"""Distributed runtime: shard_map k-means equivalence, elastic reshard,
LM train-step cross-mesh lowering (8 host devices)."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.launch.mesh import make_test_mesh
from repro.cluster import SphericalKMeans
from repro.distributed import mesh_fit, reshard_state, StepWatchdog


@pytest.fixture(scope="module")
def corpus_small():
    from repro.data import make_corpus, CorpusSpec
    return make_corpus(CorpusSpec(n_docs=1024, vocab=768, nt_mean=30,
                                  n_topics=12, seed=9))


def test_dist_matches_single_device(corpus_small):
    """mesh= routes the *same* estimator through the distributed loop."""
    docs, df, perm, topics = corpus_small
    mesh = make_test_mesh((4, 2), ("data", "model"))
    ref = SphericalKMeans(k=16, algo="mivi", max_iter=25, batch_size=512,
                          seed=5).fit(docs, df=df)
    km = SphericalKMeans(k=16, algo="esicp", max_iter=25, chunk_size=128,
                         mesh=mesh, seed=5).fit(docs, df=df)
    assert km.converged_
    assert km.model_.strategy == "mesh"
    assert len(km.labels_) == docs.n_docs
    assert (km.labels_ == ref.labels_).all()


def _scatter_adds(jaxpr, scope: str, trips: int = 1, stack: str = "") -> int:
    """How many scatter-adds under the name scope ``scope`` one run of
    ``jaxpr`` executes: loops (scans) count times their trip count."""
    total = 0
    for eqn in jaxpr.eqns:
        here = stack + "/" + str(eqn.source_info.name_stack)
        if eqn.primitive.name == "scatter-add" and scope in here:
            total += trips
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n = eqn.params["length"] if eqn.primitive.name == "scan" else 1
            total += _scatter_adds(sub, scope, trips * n, here)
    return total


@pytest.mark.parametrize("n_docs", [320, 512])
def test_mesh_sums_scatter_over_two_halves_for_any_chunk_count(n_docs):
    """The reference engine's cluster sums take two scatters per step,
    whether a shard holds an odd (320 rows padded to 3 chunks of 128) or
    an even (4) number of chunks, and the fit keeps the plain labels."""
    from repro.core.update import n_ub_groups
    from repro.data import make_corpus, CorpusSpec
    from repro.distributed.kmeans import make_step_fn

    docs, df, _, _ = make_corpus(CorpusSpec(n_docs=n_docs, vocab=512,
                                            nt_mean=20, n_topics=8, seed=3))
    mesh = make_test_mesh((1, 4), ("data", "model"))
    ref = SphericalKMeans(k=16, algo="mivi", max_iter=10, batch_size=128,
                          seed=1).fit(docs, df=df)
    km = SphericalKMeans(k=16, algo="esicp", max_iter=10, chunk_size=128,
                         mesh=mesh, seed=1).fit(docs, df=df)
    assert (km.labels_ == ref.labels_).all()

    n, p, d, k = -(-n_docs // 128) * 128, 8, 64, 16
    step = make_step_fn(mesh, algo="esicp", k=k, obj_chunk=128)
    args = (jnp.zeros((n, p), jnp.int32), jnp.zeros((n, p), jnp.float32),
            jnp.ones((n,), bool), jnp.zeros((n,), jnp.int32),
            jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32),
            jnp.zeros((n, n_ub_groups(k)), jnp.float32),
            jnp.zeros((d, k), jnp.float32), jnp.ones((k,), bool),
            jnp.asarray(0, jnp.int32), jnp.asarray(1.0, jnp.float32),
            jnp.asarray(1, jnp.int32))
    assert _scatter_adds(jax.make_jaxpr(step)(*args).jaxpr,
                         "update.sums") == 2


def test_dist_multipod_axes(corpus_small):
    docs, df, perm, topics = corpus_small
    mesh3 = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
    ref = SphericalKMeans(k=16, algo="mivi", max_iter=20, batch_size=512,
                          seed=2).fit(docs, df=df)
    state, hist, conv, params = mesh_fit(docs, 16, mesh3, algo="esicp",
                                         max_iter=20, obj_chunk=128, seed=2,
                                         df=df)
    assign = np.asarray(state.assign)[:docs.n_docs]
    assert (assign == ref.labels_).all()


def test_elastic_reshard(corpus_small):
    docs, df, perm, topics = corpus_small
    mesh_a = make_test_mesh((4, 2), ("data", "model"))
    state, hist, _, _ = mesh_fit(docs, 16, mesh_a, algo="esicp", max_iter=3,
                                 obj_chunk=128, seed=5, df=df)
    # node failure: continue on a smaller mesh (2×2), same model axis width
    mesh_b = make_test_mesh((2, 2), ("data", "model"))
    state_b = reshard_state(state, mesh_b)
    assert np.allclose(np.asarray(state_b.means_t), np.asarray(state.means_t))
    # and the resharded state keeps iterating
    from repro.distributed.kmeans import make_step_fn, dist_assignment_update
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = docs.n_docs
    pad = (-n) % (2 * 128)
    sh = lambda s: NamedSharding(mesh_b, s)
    ids = jax.device_put(jnp.pad(docs.ids, ((0, pad), (0, 0))), sh(P(("data",), None)))
    vals = jax.device_put(jnp.pad(docs.vals, ((0, pad), (0, 0))), sh(P(("data",), None)))
    valid = jax.device_put(jnp.arange(n + pad) < n, sh(P(("data",))))
    step = make_step_fn(mesh_b, algo="esicp", k=16, obj_chunk=128)
    state_b = dataclasses.replace(
        state_b,
        assign=jax.device_put(state_b.assign, sh(P(("data",)))),
        rho_self=jax.device_put(state_b.rho_self, sh(P(("data",)))),
        rho_prev=jax.device_put(state_b.rho_prev, sh(P(("data",)))))
    new, diag = dist_assignment_update(step, state_b, ids, vals, valid,
                                       jnp.asarray(0), jnp.asarray(1.0))
    assert np.isfinite(float(diag["objective"]))


def test_watchdog():
    wd = StepWatchdog(factor=3.0, warmup=2)
    import time
    for _ in range(3):
        wd.start(); time.sleep(0.01); assert wd.stop() is False
    wd.start(); time.sleep(0.08)
    assert wd.stop() is True          # 8x the median -> straggler


def test_lm_train_step_lowers_on_mesh():
    """Reduced-arch train step lowers+compiles on a 2x4 mesh with the
    production sharding rules (mini dry-run executed in-process)."""
    from repro.configs import smoke_config
    from repro.launch.steps import build_cell
    from repro.launch.shapes import ShapeSpec
    cfg = smoke_config("qwen2.5-32b")
    mesh = make_test_mesh((2, 4), ("data", "model"))
    shape = ShapeSpec("train_tiny", "train", 64, 8)
    cell = build_cell(cfg, mesh, shape, microbatches=2)
    with mesh:
        compiled = cell.fn.lower(*cell.args).compile()
    from repro.roofline.analysis import cost_dict
    assert cost_dict(compiled.cost_analysis()).get("flops", 0) > 0


def test_assign_service_matches_core(corpus_small):
    """Serving mode (frozen index lookup) == core exact assignment."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import SphericalKMeans
    from repro.core.assignment import assignment_step
    from repro.distributed.kmeans import make_assign_fn

    docs, df, perm, topics = corpus_small
    fit = SphericalKMeans(k=16, algo="esicp", max_iter=8, batch_size=512,
                          seed=5).fit(docs, df=df)
    idx = fit.state_.index
    mesh = make_test_mesh((4, 2), ("data", "model"))
    n = docs.n_docs
    pad = (-n) % (4 * 128)
    sh = lambda s: NamedSharding(mesh, s)
    ids = jax.device_put(jnp.pad(docs.ids, ((0, pad), (0, 0))),
                         sh(P(("data",), None)))
    vals = jax.device_put(jnp.pad(docs.vals, ((0, pad), (0, 0))),
                          sh(P(("data",), None)))
    valid = jax.device_put(jnp.arange(n + pad) < n, sh(P(("data",))))
    means_t = jax.device_put(idx.means_t, sh(P(None, "model")))
    fn = make_assign_fn(mesh, k=16, obj_chunk=128)
    assign, sims = fn(ids, vals, valid, means_t,
                      idx.params.t_th, idx.params.v_th)
    ref = assignment_step("mivi", docs, idx,
                          jnp.zeros((n,), jnp.int32),
                          jnp.full((n,), -jnp.inf),
                          jnp.zeros((n,), bool))
    assert (np.asarray(assign)[:n] == np.asarray(ref.assign)).all()
    np.testing.assert_allclose(np.asarray(sims)[:n], np.asarray(ref.rho),
                               rtol=1e-5, atol=1e-5)

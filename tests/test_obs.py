"""The program's own tracing (``repro.obs``): spans in the profiler's trace,
per-fit counters in the history, and phase names on the compiled ops, for
the single-host Lloyd fit and the K-sharded mesh fit."""
import glob
import re

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.cluster import ClusterConfig, fit
from repro.core import lloyd

CFG = ClusterConfig(k=8, max_iter=6, seed=0, batch_size=256)
SPANS = {"repro.fit", "repro.lloyd.prepare", "repro.lloyd.iteration",
         "repro.estparams", "repro.lloyd.pull", "repro.lloyd.fused",
         "repro.lloyd.finish"}
COUNTERS = ("compiles", "compile_s", "cache_hits", "estparams_s")


@pytest.fixture(scope="module")
def docs():
    from repro.data import CorpusSpec, make_corpus
    return make_corpus(CorpusSpec(n_docs=600, vocab=512, nt_mean=20,
                                  n_topics=8, seed=1))[0]


def _repro_events(path: str) -> list:
    """[(name, start_ns, end_ns, args)] of the host events named repro.*"""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(obs.PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_a_fit_puts_nested_spans_with_their_args_into_the_profile(
        docs, tmp_path):
    fit(docs, CFG)                       # warm, so the traced fit is short
    with jax.profiler.trace(str(tmp_path)):
        model = fit(docs, CFG)
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = _repro_events(path)
    assert {e[0] for e in events} == SPANS
    assert model.n_iter > 2              # the fused remainder ran

    by = {name: [e for e in events if e[0] == name] for name in SPANS}
    [top] = by["repro.fit"]
    assert all(e[3]["fit"] == top[3]["fit"] for e in events)
    assert all(_inside(e, top) for e in events)
    iters = sorted(by["repro.lloyd.iteration"], key=lambda e: e[1])
    assert [e[3]["iteration"] for e in iters] == [1, 2]
    assert len(by["repro.estparams"]) == 2
    for est, it in zip(sorted(by["repro.estparams"], key=lambda e: e[1]),
                       iters):
        assert _inside(est, it)
    [fused] = by["repro.lloyd.fused"]
    pulls = by["repro.lloyd.pull"]
    assert len(pulls) == 3               # one per prologue iteration + fused
    assert sum(_inside(p, fused) for p in pulls) == 1
    assert sum(_inside(p, it) for p in pulls for it in iters) == 2


def test_history_compiles_sum_to_what_a_listener_counted(docs):
    seen = {"compiles": 0, "compile_s": 0.0, "hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["compiles"] += 1
            seen["compile_s"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        # k=7 has shapes no other fit here used: the fit compiles.
        res = lloyd.lloyd_fit(docs, k=7, max_iter=5, batch_size=256, seed=3)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
    rows = res.history
    assert sum(r["compiles"] for r in rows) == seen["compiles"] > 0
    assert sum(r["compile_s"] for r in rows) == pytest.approx(
        seen["compile_s"])
    assert sum(r["cache_hits"] for r in rows) == seen["hits"]


def test_each_row_holds_its_share_of_the_fit(docs):
    fit(docs, CFG)
    rows = fit(docs, CFG).history        # warm: no compile outside EstParams
    assert len(rows) > 2
    assert all(set(COUNTERS) <= set(r) for r in rows)
    # EstParams runs in iterations 1 and 2 only, and its span is timed.
    assert all(r["estparams_s"] > 0 for r in rows[:2])
    assert all(r["estparams_s"] == 0 for r in rows[2:])
    assert all(r["compiles"] == 0 and r["compile_s"] == 0 for r in rows[2:])


def test_counters_count_only_while_a_fit_is_open():
    assert obs.open_fit() is None
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
    with obs.fit() as rec:
        with obs.fit() as inner:
            assert inner is rec          # a fit inside a fit joins it
        jax.jit(lambda x: x * 5 - 2)(jnp.arange(7.0)).block_until_ready()
        with obs.span("probe", step=1):
            pass
    assert obs.open_fit() is None
    assert rec.compiles + rec.cache_hits >= 1
    assert rec.span_s["probe"] >= 0 and set(rec.span_s) == {"probe"}
    with obs.fit() as other:
        assert other.id != rec.id


def test_the_fused_fit_compiles_under_its_name_with_phase_scopes(docs):
    from repro.core.meanindex import StructuralParams
    from repro.core.update import init_state

    k, bs = 8, 200                       # 600 rows: no padding needed
    state = init_state(docs, k, StructuralParams.trivial(docs.dim), seed=0)
    valid = jnp.arange(docs.n_docs) < docs.n_docs
    hlo = lloyd._fused_fit_fn("esicp", "reference", bs, k, 3).lower(
        state, docs, valid, jnp.asarray(1, jnp.int32), None
    ).compile().as_text()
    assert hlo.startswith("HloModule jit_lloyd_fused_fit,")
    op_names = set(re.findall(r'op_name="([^"]+)"', hlo))
    for scope in ("assign", "update.sums", "update.normalize", "update.index",
                  "update.rho", "update.bounds", "lloyd.diag"):
        assert any(f"/{scope}/" in n or n.endswith(f"/{scope}")
                   for n in op_names), scope


# -- the mesh fit -------------------------------------------------------------

MESH_SPANS = {"repro.fit", "repro.mesh.prepare", "repro.mesh.iteration",
              "repro.estparams", "repro.mesh.pull", "repro.mesh.finish"}


@pytest.fixture(scope="module")
def mesh_cfg():
    from repro.launch.mesh import make_test_mesh

    return ClusterConfig(k=8, max_iter=5, seed=0, chunk_size=128,
                         mesh=make_test_mesh((1, 4), ("data", "model")))


def test_a_mesh_fit_puts_its_spans_under_one_fit(docs, mesh_cfg, tmp_path):
    fit(docs, mesh_cfg)
    with jax.profiler.trace(str(tmp_path)):
        model = fit(docs, mesh_cfg)
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = _repro_events(path)
    assert {e[0] for e in events} == MESH_SPANS
    by = {name: [e for e in events if e[0] == name] for name in MESH_SPANS}
    [top] = by["repro.fit"]
    assert all(e[3]["fit"] == top[3]["fit"] for e in events)
    assert all(_inside(e, top) for e in events)
    iters = sorted(by["repro.mesh.iteration"], key=lambda e: e[1])
    assert [e[3]["iteration"] for e in iters] == list(
        range(1, model.n_iter + 1))
    assert len(by["repro.mesh.pull"]) == model.n_iter
    assert all(sum(_inside(p, it) for p in by["repro.mesh.pull"]) == 1
               for it in iters)
    assert all(any(_inside(est, it) for it in iters[:2])
               for est in by["repro.estparams"])


def test_each_mesh_row_holds_its_counters_and_a_warm_fit_compiles_nothing(
        docs, mesh_cfg):
    fit(docs, mesh_cfg)
    rows = fit(docs, mesh_cfg).history
    assert len(rows) > 2
    for r in rows:
        assert set(COUNTERS) | {"elapsed_s", "collective_bytes"} <= set(r)
        assert r["elapsed_s"] > 0
    assert all(r["estparams_s"] > 0 for r in rows[:2])
    assert all(r["estparams_s"] == 0 for r in rows[2:])
    assert all(r["compiles"] == 0 for r in rows)


def _collective_bytes_of(jaxpr, sizes: dict, trips: int = 1) -> int:
    """Bytes the cross-device reductions of a jaxpr carry per device,
    walking into loops (times their trip count) and calls: each
    reduction's operands, where its axes span more than one device."""
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("psum", "pmax", "pmin", "psum_invariant"):
            axes = eqn.params["axes"]
            group = int(np.prod([sizes[a] for a in axes]))
            if group > 1:
                total += trips * sum(v.aval.size * v.aval.dtype.itemsize
                                     for v in eqn.invars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n = eqn.params["length"] if name == "scan" else 1
            total += _collective_bytes_of(sub, sizes, trips * n)
    return total


@pytest.mark.parametrize("algo", ["esicp", "bounds-esicp"])
def test_collective_bytes_are_the_steps_shape_arithmetic(algo):
    from repro.core.update import n_ub_groups
    from repro.distributed.kmeans import make_step_fn, step_collective_bytes
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh((1, 4), ("data", "model"))
    n, p, d, k, chunk = 512, 8, 64, 16, 128
    g = n_ub_groups(k)
    step = make_step_fn(mesh, algo=algo, k=k, obj_chunk=chunk)
    args = (jnp.zeros((n, p), jnp.int32), jnp.zeros((n, p), jnp.float32),
            jnp.ones((n,), bool), jnp.zeros((n,), jnp.int32),
            jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32),
            jnp.zeros((n, g), jnp.float32), jnp.zeros((d, k), jnp.float32),
            jnp.ones((k,), bool), jnp.asarray(0, jnp.int32),
            jnp.asarray(1.0, jnp.float32), jnp.asarray(1, jnp.int32))
    walked = _collective_bytes_of(jax.make_jaxpr(step)(*args).jaxpr,
                                  dict(mesh.shape))
    # (best, id) per row, the group bounds of the bounds modes, the
    # candidate count, then ρ_self and the group drift; the object-axis
    # sums are zero bytes on a 'data' axis of one.
    per_doc = 8 + (4 * g if algo == "bounds-esicp" else 0)
    expected = n * per_doc + 4 + n * 4 + g * 4
    assert step_collective_bytes(mesh, algo=algo, k=k, dim=d,
                                 n_rows=n) == walked == expected


def test_a_mesh_fit_is_bit_identical_with_the_profiler_on(docs, mesh_cfg,
                                                          tmp_path):
    plain = fit(docs, mesh_cfg)
    with jax.profiler.trace(str(tmp_path)):
        traced = fit(docs, mesh_cfg)
    assert np.array_equal(plain.labels, traced.labels)
    assert np.array_equal(np.asarray(plain.index.means_t),
                          np.asarray(traced.index.means_t))


def test_the_mesh_step_compiles_under_its_name_with_phase_scopes(mesh_cfg):
    from repro.core.update import n_ub_groups
    from repro.distributed.kmeans import make_step_fn

    n, p, d, k = 256, 8, 64, 8
    step = make_step_fn(mesh_cfg.mesh, algo="esicp", k=k, obj_chunk=128)
    hlo = step.lower(
        jnp.zeros((n, p), jnp.int32), jnp.zeros((n, p), jnp.float32),
        jnp.ones((n,), bool), jnp.zeros((n,), jnp.int32),
        jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32),
        jnp.zeros((n, n_ub_groups(k)), jnp.float32),
        jnp.zeros((d, k), jnp.float32), jnp.ones((k,), bool),
        jnp.asarray(0, jnp.int32), jnp.asarray(1.0, jnp.float32),
        jnp.asarray(1, jnp.int32)).compile().as_text()
    assert hlo.startswith("HloModule jit_mesh_step,")
    op_names = set(re.findall(r'op_name="([^"]+)"', hlo))
    for scope in ("assign", "update.sums", "update.normalize", "update.rho",
                  "mesh.collective"):
        assert any(f"/{scope}/" in n or n.endswith(f"/{scope}")
                   for n in op_names), scope

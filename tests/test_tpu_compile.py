"""Compile the main path for a described TPU v5e chip, without a chip.

The TPU compiler is installed even where no TPU is attached: these tests
lower and compile the Pallas clustering kernels (``interpret=False``) at
the widths ``chip_smoke.py`` runs (B=4096 per batch, P=128, pubmed8m's
D=141,043, K=4096), the fused fit program that chose that K, and the mesh
step on the four chips of a 2x2 host at nyt1m's D=495,126 and K=10,000.
They catch the refusals interpret mode never shows (unlowerable
primitives, VMEM and SMEM limits) and pin what each program needs of a
chip's 16 GB of HBM, the figures behind the smoke's cuts.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, so describing it while the
test workers collect would fail every worker but one.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp

B, P, D, K = 4096, 128, 141_043, 4096          # chip_smoke.py's one-chip run
NYT_D, NYT_K, NYT_P, NYT_ROWS = 495_126, 10_000, 430, 16_384
HBM_BYTES = 16e9                               # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    # Compiles for a described chip are written to, but cannot be read
    # back from, the persistent cache: keep it out of these tests.
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)


def _bytes(compiled) -> float:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()      # a Mosaic kernel
    assert _bytes(compiled) <= HBM_BYTES
    return compiled


def test_sparse_sim_compiles_for_v5e(shape):
    from repro.kernels import ops

    _compile_kernel(
        lambda i, v, m: ops.sparse_sim(i, v, m, diag=True, interpret=False),
        shape((B, P), jnp.int32), shape((B, P), jnp.float32),
        shape((D, K), jnp.float32))


def test_esicp_gather_compiles_for_v5e(shape):
    from repro.kernels import ops

    _compile_kernel(
        lambda i, v, m, t, vt: ops.esicp_gather(
            i, v, m, t, vt, with_sims=True, diag=True, interpret=False),
        shape((B, P), jnp.int32), shape((B, P), jnp.float32),
        shape((D, K), jnp.float32), shape((), jnp.int32),
        shape((), jnp.float32))


def test_segment_update_compiles_for_v5e(shape):
    """Over a whole 131,072-row corpus, as the update phase calls it: the
    occupancy map is then too big for SMEM in one launch, so the wrapper
    runs row chunks that carry the accumulator."""
    from repro.kernels import ops

    n = 131_072
    _compile_kernel(
        lambda a, i, v: ops.segment_update(a, i, v, k=K, d=D,
                                           interpret=False),
        shape((n,), jnp.int32), shape((n, P), jnp.int32),
        shape((n, P), jnp.float32))


def test_rho_gather_compiles_for_v5e(shape):
    from repro.kernels import ops

    n = 131_072
    _compile_kernel(
        lambda a, i, v, m: ops.rho_gather(a, i, v, m, interpret=False),
        shape((n,), jnp.int32), shape((n, P), jnp.int32),
        shape((n, P), jnp.float32), shape((D, K), jnp.float32))


N_FIT = [131_072, -(-1_000_000 // B) * B]      # 10^6 padded to whole batches


def _fused_fit_bytes(shape, n: int, k: int) -> tuple:
    """Arguments and temporaries of the fused Lloyd program of the pallas
    esicp fit (``core/lloyd._fused_fit_body``) at N=n, K=k.  On the chip
    its state is donated, so outputs alias the arguments."""
    from repro.core import lloyd
    from repro.core.meanindex import StructuralParams
    from repro.core.update import init_state
    from repro.kernels.plan import KernelPlan
    from repro.sparse import SparseDocs

    def docs_of(mk):
        return SparseDocs(ids=mk((n, P), jnp.int32),
                          vals=mk((n, P), jnp.float32),
                          nnz=mk((n,), jnp.int32), dim=D)

    state = jax.eval_shape(lambda: init_state(docs_of(jnp.zeros), k,
                                              StructuralParams.trivial(D)))
    state = jax.tree_util.tree_map(lambda a: shape(a.shape, a.dtype), state)
    plan = KernelPlan(occ=shape((n // 128, -(-D // 256)), jnp.int32),
                      head=None, headc=None, dim=D)
    fn = lloyd._fused_fit_body
    compiled = jax.jit(
        lambda s, d, v, c, p: fn(s, d, v, c, p, algo="esicp",
                                 backend="pallas", bs=B, k=k, max_steps=2)
    ).lower(state, docs_of(shape), shape((n,), jnp.bool_),
            shape((), jnp.int32), plan).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    return ma.argument_size_in_bytes, ma.temp_size_in_bytes


@pytest.mark.parametrize("n", N_FIT)
def test_fused_fit_fits_one_v5e(shape, monkeypatch, n):
    """The smoke's K=4096 at its default corpus size and at 10^6
    documents: arguments + temporaries are 2.46 + 7.02 GB (N=131,072) and
    3.44 + 8.00 GB (N=1,003,520), headroom under 16 GB for the corpus and
    the prologue's programs (DESIGN.md section 5)."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    arg, temp = _fused_fit_bytes(shape, n, K)
    assert arg + temp <= 14e9


@pytest.mark.parametrize("n", N_FIT)
def test_fused_fit_at_k8192_exceeds_one_v5e(shape, monkeypatch, n):
    """Why the smoke cuts K to 4096: at K=8192 the same program needs
    4.77 + 13.62 GB (N=131,072) and 5.75 + 14.94 GB (N=1,003,520), more
    than one chip's 16 GB at either size."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    arg, temp = _fused_fit_bytes(shape, n, 2 * K)
    assert arg + temp > HBM_BYTES


def _compile_mesh_step(topo, backend: str):
    """The mesh step at nyt1m's width (P=430, the corpus's padded slot
    count, which the kernel wrappers round up to 432), K sharded four ways
    over the chips of a 2x2 host: the (D, K) index is 19.8 GB in all,
    4.95 GB per chip.  The pallas step takes the prepared-plan operands
    ``mesh_fit`` builds (occupancy map, head slabs)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as Ps

    from repro.core.update import n_ub_groups
    from repro.distributed.kmeans import PlanMeta, make_step_fn
    from repro.kernels import plan as kplan

    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    spec = lambda s, dt, p: jax.ShapeDtypeStruct(
        s, dt, sharding=NamedSharding(mesh, p))
    obj = ("data",)
    n = NYT_ROWS
    meta, plan_args = None, ()
    if backend == "pallas":
        n_head = kplan.pick_n_head(n, NYT_D, with_counts=False)
        meta = PlanMeta(b_blk=kplan.DEFAULT_B_BLK, d_blk=kplan.DEFAULT_D_BLK,
                        n_head=n_head, dim=NYT_D)
        nd = -(-NYT_D // meta.d_blk)
        plan_args = (spec((n // meta.b_blk, nd), jnp.int32, Ps(obj, None)),)
        if n_head:
            plan_args += (spec((n, n_head * meta.d_blk), jnp.float32,
                               Ps(obj, None)),)
    step = make_step_fn(mesh, algo="esicp", k=NYT_K, obj_chunk=1024,
                        backend=backend, plan_meta=meta)
    args = (spec((n, NYT_P), jnp.int32, Ps(obj, None)),
            spec((n, NYT_P), jnp.float32, Ps(obj, None)),
            spec((n,), jnp.bool_, Ps(obj)),
            spec((n,), jnp.int32, Ps(obj)),
            spec((n,), jnp.float32, Ps(obj)),
            spec((n,), jnp.float32, Ps(obj)),
            spec((n, n_ub_groups(NYT_K)), jnp.float32, Ps(obj, None)),
            spec((NYT_D, NYT_K), jnp.float32, Ps(None, "model")),
            spec((NYT_K,), jnp.bool_, Ps("model")),
            spec((), jnp.int32, Ps()), spec((), jnp.float32, Ps()),
            spec((), jnp.int32, Ps())) + plan_args
    compiled = step.lower(*args).compile()
    # The index is sharded, not replicated: each chip holds a quarter.
    assert compiled.memory_analysis().argument_size_in_bytes < 6e9
    return compiled


def test_mesh_step_compiles_for_2x2_v5e(topo):
    """The reference step fits a chip: 5.02 GB of arguments, 4.96 GB of
    outputs (the new means) and 5.10 GB of temporaries per chip."""
    assert _bytes(_compile_mesh_step(topo, "reference")) <= HBM_BYTES


def test_mesh_step_pallas_compiles_for_2x2_v5e(topo, monkeypatch):
    """The kernels users get by default on TPU (``backend="auto"``) in the
    mesh step at nyt1m's width, 2,500 centroids per chip.  It lowers, but
    needs 20.2 GB per chip: 10.18 GB of temporaries against the reference
    step's 5.10, because the kernel wrappers pad the (D, K_loc) index and
    the cluster sums to (495,360, 2,560), 5.07 GB each.  So
    ``chip_smoke.py --four-chips`` runs the reference engine at this width
    (DESIGN.md section 5)."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    compiled = _compile_mesh_step(topo, "pallas")
    assert "tpu_custom_call" in compiled.as_text()

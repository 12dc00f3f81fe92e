"""Hypothesis sweep of NON-block-aligned shapes through every clustering
wrapper in kernels/ops.py (ISSUE 5 satellite) AND their compiled
kernels/xla_blocked.py twins (ISSUE 10 satellite).

The wrappers promise: pad to block multiples, launch, slice back — for ANY
logical (B, K, D, P), including P that is not an 8-multiple (the kernels'
one hard alignment) and B/K/D that straddle block boundaries, with or
without a prepared plan, with or without the fused diagnostics.  This file
pins that padding/slicing contract against the pure-jnp oracles so a grid
or BlockSpec change can never silently narrow it.  The xla_blocked twins
ride the same ragged cases (their internal padding is the P-chunk split +
the head-plan D padding) and accept the Pallas geometry kwargs as inert
compatibility arguments — asserted here by passing them.
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, strategies as st

import jax.numpy as jnp

from repro.kernels import (sparse_sim, esicp_gather, esicp_filter,
                           segment_update, rho_gather, ref)
from repro.kernels import xla_blocked as xb
from repro.kernels.plan import prepare_plan

hypothesis.settings.register_profile(
    "kernel-pad", deadline=None, max_examples=20,
    suppress_health_check=[hypothesis.HealthCheck.too_slow])
hypothesis.settings.load_profile("kernel-pad")

# Small blocks so modest shapes straddle many block boundaries.
BLK = dict(b_blk=32, k_blk=32, d_blk=64)


@st.composite
def ragged_case(draw):
    b = draw(st.integers(1, 70))
    p = draw(st.integers(1, 19))           # includes every P % 8 residue
    d = draw(st.integers(3, 200))
    k = draw(st.integers(1, 70))
    seed = draw(st.integers(0, 2**31 - 1))
    use_plan = draw(st.booleans())
    rng = np.random.default_rng(seed)
    ids = np.sort(rng.integers(0, d, (b, p)), axis=1).astype(np.int32)
    vals = rng.random((b, p)).astype(np.float32)
    nnz = rng.integers(1, p + 1, b)
    for i in range(b):
        vals[i, nnz[i]:] = 0.0
        ids[i, nnz[i]:] = 0
    means_t = np.where(rng.random((d, k)) < 0.3,
                       rng.random((d, k)), 0.0).astype(np.float32)
    # includes the out-of-range padding convention assign == k
    assign = rng.integers(0, k + 1, b).astype(np.int32)
    t_th = draw(st.integers(0, d))
    v_th = draw(st.floats(0.05, 0.95))
    plan = None
    if use_plan:
        plan = prepare_plan(ids, vals, dim=d, b_blk=BLK["b_blk"],
                            d_blk=BLK["d_blk"], head_bytes=1 << 30)
    return (jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(means_t),
            jnp.asarray(assign), t_th, v_th, plan)


@given(ragged_case())
def test_sparse_sim_any_shape(case):
    ids, vals, means_t, assign, t_th, v_th, plan = case
    sims, counts = sparse_sim(ids, vals, means_t, plan=plan, diag=True, **BLK)
    assert sims.shape == (ids.shape[0], means_t.shape[1])
    np.testing.assert_allclose(np.asarray(sims),
                               np.asarray(ref.sparse_sim(ids, vals, means_t)),
                               rtol=1e-4, atol=1e-4)
    live01 = (np.asarray(vals) != 0).astype(np.float32)
    expc = ref.sparse_sim(ids, jnp.asarray(live01),
                          (means_t > 0).astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(counts), np.asarray(expc),
                               rtol=1e-4, atol=1e-4)


@given(ragged_case())
def test_esicp_gather_any_shape(case):
    ids, vals, means_t, assign, t_th, v_th, plan = case
    r12, y, sims = esicp_gather(ids, vals, means_t, t_th, v_th, plan=plan,
                                with_sims=True, **BLK)
    e12, ey = ref.esicp_gather(ids, vals, means_t, t_th, v_th)
    np.testing.assert_allclose(np.asarray(r12), np.asarray(e12),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ey),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sims),
                               np.asarray(ref.sparse_sim(ids, vals, means_t)),
                               rtol=1e-4, atol=1e-4)


@given(ragged_case())
def test_esicp_filter_any_shape(case):
    ids, vals, means_t, assign, t_th, v_th, plan = case
    b, k = ids.shape[0], means_t.shape[1]
    rng = np.random.default_rng(0)
    rho12 = jnp.asarray(rng.random((b, k)).astype(np.float32))
    y = jnp.asarray(rng.random((b, k)).astype(np.float32))
    rho_max = jnp.asarray(rng.random(b).astype(np.float32))
    col_ok = jnp.asarray(rng.random((b, k)) < 0.7)
    m, c = esicp_filter(rho12, y, rho_max, col_ok, v_th,
                        b_blk=BLK["b_blk"], k_blk=BLK["k_blk"])
    em, ec = ref.esicp_filter(rho12, y, rho_max, col_ok, v_th)
    assert np.array_equal(np.asarray(m), np.asarray(em))
    assert np.array_equal(np.asarray(c), np.asarray(ec))


@given(ragged_case())
def test_segment_update_any_shape(case):
    ids, vals, means_t, assign, t_th, v_th, plan = case
    k, d = means_t.shape[1], means_t.shape[0]
    lam = segment_update(assign, ids, vals, k=k, d=d, plan=plan, **BLK)
    assert lam.shape == (k, d)
    x = np.asarray(ref.densify(ids, vals, d))
    exp = np.zeros((k, d), np.float32)
    for i, a in enumerate(np.asarray(assign)):
        if a < k:                       # assign == k rows contribute nothing
            exp[a] += x[i]
    np.testing.assert_allclose(np.asarray(lam), exp, rtol=1e-4, atol=1e-4)


@given(ragged_case())
def test_rho_gather_any_shape(case):
    ids, vals, means_t, assign, t_th, v_th, plan = case
    rho = rho_gather(assign, ids, vals, means_t, plan=plan, **BLK)
    exp = ref.rho_gather(assign, ids, vals, means_t)
    np.testing.assert_allclose(np.asarray(rho), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)
    assert (np.asarray(rho)[np.asarray(assign) == means_t.shape[1]]
            == 0.0).all()


# ---------------------------------------------------------------------------
# xla_blocked twins: same ragged cases, same oracles, compiled XLA engine.
# The ragged plans carry head slabs (head_bytes=1<<30) but no count twins,
# so diag calls exercise the layout-mismatch fallback too.
# ---------------------------------------------------------------------------

@given(ragged_case())
def test_xla_sparse_sim_any_shape(case):
    ids, vals, means_t, assign, t_th, v_th, plan = case
    sims, counts = xb.sparse_sim(ids, vals, means_t, plan=plan, diag=True,
                                 **BLK)
    assert sims.shape == (ids.shape[0], means_t.shape[1])
    np.testing.assert_allclose(np.asarray(sims),
                               np.asarray(ref.sparse_sim(ids, vals, means_t)),
                               rtol=1e-4, atol=1e-4)
    live01 = (np.asarray(vals) != 0).astype(np.float32)
    expc = ref.sparse_sim(ids, jnp.asarray(live01),
                          (means_t > 0).astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(counts), np.asarray(expc),
                               rtol=1e-4, atol=1e-4)


@given(ragged_case())
def test_xla_esicp_gather_any_shape(case):
    ids, vals, means_t, assign, t_th, v_th, plan = case
    r12, y, sims = xb.esicp_gather(ids, vals, means_t, t_th, v_th, plan=plan,
                                   with_sims=True, **BLK)
    e12, ey = ref.esicp_gather(ids, vals, means_t, t_th, v_th)
    np.testing.assert_allclose(np.asarray(r12), np.asarray(e12),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ey),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sims),
                               np.asarray(ref.sparse_sim(ids, vals, means_t)),
                               rtol=1e-4, atol=1e-4)


@given(ragged_case())
def test_xla_esicp_gather_per_object_threshold(case):
    """The TA form (v_ta per object) — natively compiled in this engine;
    the head path must stay disengaged (asserted via exactness alone)."""
    ids, vals, means_t, assign, t_th, v_th, plan = case
    rng = np.random.default_rng(7)
    v_ta = rng.random(ids.shape[0]).astype(np.float32)
    r12, y = xb.esicp_gather(ids, vals, means_t, t_th, v_th,
                             v_ta=jnp.asarray(v_ta), plan=plan, **BLK)
    idn, vn, mt = np.asarray(ids), np.asarray(vals), np.asarray(means_t)
    rows = mt[idn]                                    # (B, P, K)
    tail = (idn >= t_th)[..., None]
    hi = rows >= v_ta[:, None, None]
    exact = np.where(tail, hi, True)
    e12 = np.sum(np.where(exact, vn[..., None] * rows, 0.0), axis=1)
    ey = np.sum(np.where(tail & ~hi, vn[..., None], 0.0), axis=1)
    np.testing.assert_allclose(np.asarray(r12), e12, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y), ey, rtol=1e-4, atol=1e-4)


@given(ragged_case())
def test_xla_cs_gather_any_shape(case):
    """The fused CS op vs slot-semantics oracles: rho1 drops tail-slot
    contributions, sq sums means² over the live slots with id >= t_th —
    dead slots, the caller's or the op's internal chunk padding, add
    nothing (the reference scan's semantics)."""
    ids, vals, means_t, assign, t_th, v_th, plan = case
    sims, rho1, sq, counts = xb.cs_gather(ids, vals, means_t, t_th,
                                          plan=plan, diag=True)
    np.testing.assert_allclose(np.asarray(sims),
                               np.asarray(ref.sparse_sim(ids, vals, means_t)),
                               rtol=1e-4, atol=1e-4)
    head_vals = jnp.where(ids >= t_th, 0.0, vals)
    np.testing.assert_allclose(
        np.asarray(rho1),
        np.asarray(ref.sparse_sim(ids, head_vals, means_t)),
        rtol=1e-4, atol=1e-4)
    tail01 = ((np.asarray(ids) >= t_th)
              & (np.asarray(vals) != 0)).astype(np.float32)  # live slots
    np.testing.assert_allclose(
        np.asarray(sq),
        np.asarray(ref.sparse_sim(ids, jnp.asarray(tail01), means_t ** 2)),
        rtol=1e-4, atol=1e-4)
    live01 = (np.asarray(vals) != 0).astype(np.float32)
    expc = ref.sparse_sim(ids, jnp.asarray(live01),
                          (means_t > 0).astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(counts), np.asarray(expc),
                               rtol=1e-4, atol=1e-4)


@given(ragged_case())
def test_xla_segment_update_any_shape(case):
    ids, vals, means_t, assign, t_th, v_th, plan = case
    k, d = means_t.shape[1], means_t.shape[0]
    lam = xb.segment_update(assign, ids, vals, k=k, d=d, plan=plan, **BLK)
    assert lam.shape == (k, d)
    np.testing.assert_allclose(
        np.asarray(lam), np.asarray(ref.segment_update(assign, ids, vals,
                                                       k, d)),
        rtol=1e-4, atol=1e-4)


@given(ragged_case())
def test_xla_rho_gather_any_shape(case):
    ids, vals, means_t, assign, t_th, v_th, plan = case
    rho = xb.rho_gather(assign, ids, vals, means_t, plan=plan, **BLK)
    exp = ref.rho_gather(assign, ids, vals, means_t)
    np.testing.assert_allclose(np.asarray(rho), np.asarray(exp),
                               rtol=1e-4, atol=1e-4)
    assert (np.asarray(rho)[np.asarray(assign) == means_t.shape[1]]
            == 0.0).all()

"""The reference engine's slot loop runs each tile only to its live width,
and the resident fit scans tiles of documents of like length.

Both change which steps run, never what a document's sums add: a dead slot
adds exact zeros, and reordering tiles does not reorder any document's own
slots.  So every accumulator, label, mean and ρ_self is bit-identical to a
scan over all P slots of the tiles in row order, which these tests pin.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import StructuralParams, build_mean_index, lloyd
from repro.core import backends
from repro.core.backends import live_steps, reference_scan
from repro.core.update import init_state
from repro.sparse import SparseDocs, pad_rows

P, D, K = 10, 64, 9
# Mixed lengths: empty rows, a row that fills P, and rows in between.
NNZ = [0, 10, 3, 0, 7, 1, 5, 10, 2, 0, 4, 6]
SHORT = [2, 0, 5, 1, 3, 0]                    # a tile whose longest row < P


def _docs(nnz, seed=0):
    rng = np.random.default_rng(seed)
    b = len(nnz)
    ids = np.zeros((b, P), np.int32)
    vals = np.zeros((b, P), np.float32)
    for i, m in enumerate(nnz):
        ids[i, :m] = np.sort(rng.choice(D, m, replace=False))
        v = rng.random(m).astype(np.float32) + 0.05
        vals[i, :m] = v / np.linalg.norm(v) if m else v
    return SparseDocs(ids=jnp.asarray(ids), vals=jnp.asarray(vals),
                      nnz=jnp.asarray(nnz, jnp.int32), dim=D)


def _index(t_th, seed=1):
    rng = np.random.default_rng(seed)
    means = np.where(rng.random((K, D)) < 0.4, rng.random((K, D)), 0.0)
    means /= np.maximum(np.linalg.norm(means, axis=1, keepdims=True), 1e-9)
    params = StructuralParams(t_th=jnp.asarray(t_th, jnp.int32),
                              v_th=jnp.asarray(0.2, jnp.float32))
    moving = jnp.asarray(np.arange(K) % 2 == 0)
    return build_mean_index(jnp.asarray(means, jnp.float32), params,
                            moving=moving)


CASES = ([(m, d, False) for m in ("exact", "esicp", "ta", "cs")
          for d in (True, False)]
         + [("exact", True, True), ("esicp", True, True)])


@pytest.mark.parametrize("p_block", [1, 3])
@pytest.mark.parametrize("t_th", [0, D // 2])
@pytest.mark.parametrize("tile", [NNZ, SHORT], ids=["fills_p", "short"])
@pytest.mark.parametrize("mode,diag,with_counts", CASES)
def test_live_width_loop_is_the_full_scan_bit_for_bit(mode, diag, with_counts,
                                                      tile, t_th, p_block):
    docs = _docs(tile)
    index = _index(t_th)
    b = docs.n_docs
    xstate = jnp.asarray(np.arange(b) % 3 == 0)
    v_ta = (jnp.linspace(0.05, 0.4, b).astype(jnp.float32)
            if mode == "ta" else None)
    kw = dict(mode=mode, v_ta=v_ta, diag=diag, p_block=p_block,
              with_counts=with_counts)
    live = reference_scan(docs, index, xstate, **kw)
    full = reference_scan(docs, index, xstate, unroll=1, **kw)  # static, all P
    assert set(live) == set(full)
    for key in full:
        np.testing.assert_array_equal(np.asarray(live[key]),
                                      np.asarray(full[key]), err_msg=key)


def test_live_steps_counts_blocks_to_the_longest_row():
    nnz = jnp.asarray([0, 3, 5, 1], jnp.int32)
    assert int(live_steps(nnz, 8)) == 5
    assert int(live_steps(nnz, 8, 3)) == 2
    assert int(live_steps(jnp.zeros((4,), jnp.int32), 8)) == 0
    assert int(live_steps(jnp.asarray([8, 2], jnp.int32), 8, 3)) == 3


def test_slots_past_the_longest_row_are_never_read():
    """The loop stops at max(nnz): values planted past it (which a valid
    corpus never holds) change the full scan but not the live one."""
    docs = _docs(SHORT)
    planted = dataclasses.replace(docs, vals=docs.vals.at[:, P - 1].set(1.0),
                                  ids=docs.ids.at[:, P - 1].set(D - 1))
    index = _index(0)
    xs = jnp.zeros((docs.n_docs,), bool)
    clean = reference_scan(docs, index, xs, mode="exact")["sims"]
    live = reference_scan(planted, index, xs, mode="exact")["sims"]
    full = reference_scan(planted, index, xs, mode="exact", unroll=1)["sims"]
    np.testing.assert_array_equal(np.asarray(live), np.asarray(clean))
    assert not np.array_equal(np.asarray(full), np.asarray(clean))


def test_cs_square_sum_counts_live_tail_slots_only():
    """At t_th = 0 every slot is a tail slot; dead slots (id 0) must not add
    row 0's squares, so an empty row's ``sq`` is 0 (Σ over its live slots)."""
    docs = _docs(NNZ)
    index = _index(0)
    out = reference_scan(docs, index, jnp.zeros((docs.n_docs,), bool),
                         mode="cs")
    m2 = np.asarray(index.means_t) ** 2
    ids, nnz = np.asarray(docs.ids), np.asarray(docs.nnz)
    expect = np.stack([m2[ids[i, :nnz[i]]].sum(0) for i in range(len(nnz))])
    np.testing.assert_allclose(np.asarray(out["sq"]), expect, rtol=1e-6)
    assert not np.asarray(out["sq"])[np.asarray(NNZ) == 0].any()


# ---------------------------------------------------------------------------
# The length-ordered epoch and fit.
# ---------------------------------------------------------------------------

def _varied_corpus(n=650, seed=5):
    """A corpus whose documents' lengths vary 4× (each row truncated to
    25–100% of its terms, then renormalised)."""
    from repro.data import CorpusSpec, make_corpus

    docs, df, _, _ = make_corpus(CorpusSpec(n_docs=n, vocab=512, nt_mean=24,
                                            n_topics=8, seed=seed))
    rng = np.random.default_rng(seed)
    ids, vals = np.asarray(docs.ids).copy(), np.asarray(docs.vals).copy()
    nnz = np.asarray(docs.nnz).copy()
    for i in range(n):
        m = max(2, int(round(nnz[i] * rng.uniform(0.25, 1.0))))
        ids[i, m:], vals[i, m:], nnz[i] = 0, 0.0, m
        vals[i, :m] /= np.linalg.norm(vals[i, :m])
    return SparseDocs(ids=jnp.asarray(ids), vals=jnp.asarray(vals),
                      nnz=jnp.asarray(nnz), dim=docs.dim), df


@pytest.fixture(scope="module")
def varied():
    return _varied_corpus()


@pytest.mark.parametrize("algo", ["esicp", "bounds-esicp"])
def test_epoch_in_length_order_equals_row_order(varied, algo):
    docs, _ = varied
    bs, k = 100, 8
    pdocs = pad_rows(docs, bs)
    n = pdocs.n_docs
    state = init_state(docs, k, StructuralParams.trivial(docs.dim), seed=2)
    pad = n - docs.n_docs
    state = dataclasses.replace(
        state, assign=jnp.pad(state.assign, (0, pad)),
        rho_self=jnp.pad(state.rho_self, (0, pad)),
        rho_self_prev=jnp.pad(state.rho_self_prev, (0, pad)),
        ub=jnp.pad(state.ub, ((0, pad), (0, 0))))
    valid = jnp.arange(n) < docs.n_docs
    # One iteration first, so the ICP flags, ρ_self and ub are not trivial.
    state, _ = lloyd._device_iteration(algo, "reference", pdocs, state, valid,
                                       bs=bs, k=k)
    order = lloyd.length_order(pdocs)
    assert (np.diff(np.asarray(order.docs.nnz)) >= 0).all()
    args = (algo, "reference", pdocs, state.index, state.assign,
            state.rho_self, state.xstate, valid, bs, None, state.ub)
    rows = lloyd._fused_epoch(*args)
    tiles = lloyd._fused_epoch(*args, order)
    for name, a, b in zip(("assign", "ub", "mult", "cand", "changed"),
                          rows, tiles):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@pytest.fixture
def row_order_full_scan(monkeypatch):
    """The scan without either mechanism: tiles in row order, every tile
    over all P slots.  Jitted programs traced before or under the patch
    must not outlive it."""
    jax.clear_caches()
    lloyd._fused_fit_fn.cache_clear()
    monkeypatch.setattr(lloyd, "length_order", lambda docs: None)
    monkeypatch.setattr(backends, "live_steps",
                        lambda nnz, p, pb=1: -(-p // pb))
    yield
    monkeypatch.undo()
    jax.clear_caches()
    lloyd._fused_fit_fn.cache_clear()


def _fit(docs, df, algo):
    res = lloyd.lloyd_fit(docs, k=8, algo=algo, batch_size=100, max_iter=6,
                          seed=3, df=df)
    rows = [{key: v for key, v in h.items()
             if key not in ("elapsed_s", "compiles", "compile_s",
                            "cache_hits", "estparams_s", "scan_slot_share")}
            for h in res.history]
    return (res.assign, np.asarray(res.state.index.means_t),
            np.asarray(res.state.rho_self), rows)


@pytest.mark.parametrize("algo", ["esicp", "cs-icp"])
def test_fit_is_bit_identical_to_the_row_order_full_scan(varied, algo,
                                                         request):
    """Labels, means, ρ_self and every history field equal the fit that
    scans tiles in row order over all P slots.  At these sizes ``mult`` is
    an exact integer in float32, so summing it over the tiles in another
    order changes nothing; at chip sizes it is a float32 sum over about
    3·10¹⁰ visited pairs, and its last bits may differ."""
    docs, df = varied
    tiles = _fit(docs, df, algo)
    request.getfixturevalue("row_order_full_scan")
    rows = _fit(docs, df, algo)
    np.testing.assert_array_equal(tiles[0], rows[0])
    np.testing.assert_array_equal(tiles[1], rows[1])
    np.testing.assert_array_equal(tiles[2], rows[2])
    assert tiles[3] == rows[3]


def _share(nnz, bs, p, fold):
    tiles = np.asarray(nnz).reshape(-1, bs)
    slots = -(-tiles.max(axis=1) // fold) * fold
    return slots.sum() / (tiles.shape[0] * p)


@pytest.mark.parametrize("backend", ["reference", "xla_blocked"])
def test_scan_slot_share_is_the_epochs_slot_steps(varied, backend):
    """Σ per-tile longest document, rounded up to whole folds, / (tiles × P)
    over the tiles as the epoch scans them: length-ordered on the reference
    engine, row order on an engine with a plan.  The length order is the
    smaller share."""
    docs, df = varied
    bs = 100
    nnz = np.asarray(pad_rows(docs, bs).nnz)
    p = docs.pad_width
    fold = backends.scan_fold(p)
    res = lloyd.lloyd_fit(docs, k=8, batch_size=bs, max_iter=3, seed=3,
                          df=df, backend=backend)
    expect = _share(np.sort(nnz, kind="stable") if backend == "reference"
                    else nnz, bs, p, fold)
    assert _share(np.sort(nnz), bs, p, fold) < _share(nnz, bs, p, fold)
    shares = [h["scan_slot_share"] for h in res.history]
    assert len(shares) == res.n_iter >= 3
    np.testing.assert_allclose(shares, expect, rtol=1e-6)
    assert [h["scan_fold"] for h in res.history] == [fold] * res.n_iter

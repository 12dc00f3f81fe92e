"""The reference scan folds several slots into each rewrite of its (B, K)
carries, and adds them one slot after another, in the one-slot loop's order.

So a document's slots are still added 0…nnz−1, one slot per add: the fold
changes how often the carries are written back, not the order of any sum.
``mult`` and ``counts`` are sums of exact integers and must be equal.  The
float carries are equal wherever the compiler does not contract a multiply
and an add into one rounding; XLA's CPU backend may, at any optimisation
level, so on general data they are held to 1 ulp.  On data whose products
are exact in float32 a contraction rounds nothing away, so there only the
order of the adds can move a bit, and every carry must be bit-identical.
"""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_mean_index, lloyd
from repro.core import backends
from repro.core.backends import reference_scan, scan_fold
from test_length_tiles import (CASES, D, NNZ, SHORT, _docs, _index,
                               _varied_corpus)

FOLDS = [2, 3, 8]
INTEGER = ("mult", "counts")                   # sums of exact integers


def _short(x, bits):
    """``x`` rounded to ``bits`` significant bits, so that the product of
    two such numbers is exact in float32."""
    m, e = np.frexp(np.asarray(x, np.float64))
    return jnp.asarray(np.ldexp(np.round(m * 2.0 ** bits) / 2.0 ** bits, e),
                       jnp.float32)


def _scan(tile, t_th, fold, mode, diag, with_counts, bits=None):
    docs, index = _docs(tile), _index(t_th)
    if bits is not None:
        docs = dataclasses.replace(docs, vals=_short(docs.vals, bits))
        index = build_mean_index(_short(index.means_t.T, bits), index.params,
                                 moving=index.moving)
    b = docs.n_docs
    xstate = jnp.asarray(np.arange(b) % 3 == 0)
    v_ta = (jnp.linspace(0.05, 0.4, b).astype(jnp.float32)
            if mode == "ta" else None)
    out = reference_scan(docs, index, xstate, mode=mode, v_ta=v_ta,
                         diag=diag, p_block=fold, with_counts=with_counts)
    return {key: np.asarray(v) for key, v in out.items()}


@pytest.mark.parametrize("fold", FOLDS)
@pytest.mark.parametrize("t_th", [0, D // 2])
@pytest.mark.parametrize("tile", [NNZ, SHORT], ids=["fills_p", "short"])
@pytest.mark.parametrize("mode,diag,with_counts", CASES)
def test_fold_adds_each_slot_in_the_one_slot_order(mode, diag, with_counts,
                                                   tile, t_th, fold):
    one = _scan(tile, t_th, 1, mode, diag, with_counts)
    folded = _scan(tile, t_th, fold, mode, diag, with_counts)
    assert set(folded) == set(one)
    for key in one:
        if key in INTEGER:
            np.testing.assert_array_equal(folded[key], one[key], err_msg=key)
        else:
            np.testing.assert_array_max_ulp(folded[key], one[key], maxulp=1)


_LEVEL0 = """
import jax.numpy as jnp, numpy as np, sys
sys.path[:0] = [{tests!r}, {src!r}]
import test_scan_fold as t
for mode, diag, counts in t.CASES:
    for tile in (t.NNZ, t.SHORT):
        one = t._scan(tile, t.D // 2, 1, mode, diag, counts, bits=12)
        for fold in t.FOLDS:
            folded = t._scan(tile, t.D // 2, fold, mode, diag, counts, bits=12)
            for key in one:
                assert np.array_equal(folded[key], one[key]), (mode, fold, key)
print("bit-identical")
"""


def test_fold_is_bit_identical_without_backend_optimisation():
    """With the CPU backend's optimisations off, on values of 12 significant
    bits (every product exact, so a contracted multiply-add rounds as the
    pair does), every carry of every mode and fold equals the one-slot loop
    bit for bit: the order of the adds itself is the same."""
    tests = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_backend_optimization_level=0")
    res = subprocess.run([sys.executable, "-c", _LEVEL0.format(
        tests=str(tests), src=str(tests.parent / "src"))],
                         env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "bit-identical" in res.stdout


@pytest.mark.parametrize("p,fold", [(128, 3), (432, 3), (2, 2), (1, 1)])
def test_the_fold_rule_at_the_benchmark_cells_shapes(p, fold):
    """P = 128 (pubmed8m.fit's tiles, padded to 129) and 432 (nyt1m.fit's
    tiles and nyt1m.mesh_fit's per-chip chunks) take three slots a fold,
    whatever their B and K; a tile narrower than the fold takes its own
    width."""
    assert scan_fold(p) == fold


def test_scan_slot_share_counts_whole_folds():
    """Each tile gathers its longest row's slots rounded up to whole folds:
    tiles of longest 5, 0 and 9 over P = 12 at fold 4 gather 8 + 0 + 12."""
    nnz = jnp.asarray([1, 5, 0, 0, 9, 2], jnp.int32)
    share = lloyd.scan_slot_share(nnz, p=12, bs=2, fold=4)
    assert float(share) == pytest.approx(20 / 36)
    assert float(lloyd.scan_slot_share(nnz, p=12, bs=2, fold=1)) == \
        pytest.approx(14 / 36)


# ---------------------------------------------------------------------------
# Whole fits: the derived fold against fold 1.
# ---------------------------------------------------------------------------

@pytest.fixture
def fold_one(monkeypatch):
    """The scan at one slot per rewrite.  Programs traced before or under
    the patch must not outlive it."""
    jax.clear_caches()
    lloyd._fused_fit_fn.cache_clear()
    monkeypatch.setattr(backends, "SCAN_FOLD", 1)
    yield
    monkeypatch.undo()
    jax.clear_caches()
    lloyd._fused_fit_fn.cache_clear()


@pytest.fixture(scope="module")
def varied():
    return _varied_corpus()


def _fit(docs, df, algo):
    res = lloyd.lloyd_fit(docs, k=8, algo=algo, batch_size=100, max_iter=6,
                          seed=3, df=df)
    return (res.assign, np.asarray(res.state.index.means_t),
            np.asarray(res.state.rho_self),
            [h["scan_fold"] for h in res.history])


@pytest.mark.parametrize("algo", ["esicp", "bounds-esicp"])
def test_fit_with_the_derived_fold_equals_fold_one(varied, algo, request):
    docs, df = varied
    fold = scan_fold(docs.pad_width)
    assert fold > 1
    folded = _fit(docs, df, algo)
    request.getfixturevalue("fold_one")
    one = _fit(docs, df, algo)
    assert set(folded[3]) == {fold}
    assert set(one[3]) == {1}
    np.testing.assert_array_equal(folded[0], one[0])
    np.testing.assert_array_equal(folded[1], one[1])
    np.testing.assert_array_equal(folded[2], one[2])


def test_mesh_fit_with_the_derived_fold_equals_fold_one(varied):
    """The mesh step's local scan on a one-device mesh: the fold it derives
    from its chunk's shape and a pinned fold of 1 give the same labels,
    means and ρ_self."""
    from repro.distributed import mesh_fit
    from repro.launch.mesh import make_test_mesh

    docs, df = varied
    mesh = make_test_mesh((1, 1), ("data", "model"))
    kw = dict(algo="esicp", max_iter=4, obj_chunk=128, seed=3, df=df)
    folded, _, _, _ = mesh_fit(docs, 8, mesh, **kw)
    one, _, _, _ = mesh_fit(docs, 8, mesh, p_block=1, **kw)
    for name in ("assign", "means_t", "rho_self"):
        np.testing.assert_array_equal(np.asarray(getattr(folded, name)),
                                      np.asarray(getattr(one, name)),
                                      err_msg=name)

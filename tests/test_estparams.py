"""EstParams: the estimator's J approximates measured Mult and the
structural parameters land where the paper says (t_th near D, small v_th)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import SphericalKMeans, StructuralParams
from repro.core.assignment import assignment_step
from repro.core.estparams import _v_candidates, estimate_params, EstGrid


def test_estimator_tracks_actual(small_corpus):
    docs, df, perm, topics = small_corpus
    warm = SphericalKMeans(k=24, algo="mivi", max_iter=3, batch_size=750,
                           seed=0).fit(docs, df=df)
    state = warm.state_
    grid = EstGrid(n_v=6, n_s=12)
    est, aux = estimate_params(docs, df, state.index.means_t, state.rho_self,
                               k=24, grid=grid)
    j_tab = np.asarray(aux["J"])
    s_grid = np.asarray(aux["s_grid"])
    v_grid = np.asarray(aux["v_grid"])

    approx, actual = [], []
    for hi in range(len(v_grid)):
        si = int(np.argmin(j_tab[:, hi]))
        params = StructuralParams(
            t_th=jnp.asarray(int(s_grid[si]), jnp.int32),
            v_th=jnp.asarray(float(v_grid[hi]), jnp.float32))
        idx = state.index.with_params(params)
        r = assignment_step("es", docs, idx, state.assign, state.rho_self,
                            jnp.zeros((docs.n_docs,), bool))
        approx.append(j_tab[si, hi])
        actual.append(float(r.mult))
    corr = np.corrcoef(approx, actual)[0, 1]
    assert corr > 0.6, (corr, approx, actual)


def test_structural_params_regime(small_corpus):
    docs, df, perm, topics = small_corpus
    warm = SphericalKMeans(k=24, algo="mivi", max_iter=3, batch_size=750,
                           seed=0).fit(docs, df=df)
    est, aux = estimate_params(docs, df, warm.state_.index.means_t,
                               warm.state_.rho_self, k=24)
    assert int(est.t_th) >= int(0.8 * docs.dim)     # grid floor = int(0.80·D)
    vals = warm.state_.index.means_t[warm.state_.index.means_t > 0]
    assert float(est.v_th) <= float(jnp.max(vals))
    assert float(est.v_th) > 0


def test_j_table_components_nonnegative(small_corpus):
    docs, df, perm, topics = small_corpus
    warm = SphericalKMeans(k=24, algo="mivi", max_iter=2, batch_size=750,
                           seed=0).fit(docs, df=df)
    _, aux = estimate_params(docs, df, warm.state_.index.means_t,
                             warm.state_.rho_self, k=24,
                             grid=EstGrid(n_v=5, n_s=8))
    assert (np.asarray(aux["phi1"]) >= 0).all()
    assert (np.asarray(aux["phi2"]) >= 0).all()
    assert (np.asarray(aux["phi3"]) >= 0).all()
    # φ1 grows with s' (more Region-1 terms), φ2 shrinks
    assert (np.diff(np.asarray(aux["phi1"])) >= 0).all()
    assert (np.diff(np.asarray(aux["phi2"]), axis=0) <= 1e-6).all()


def _nanquantile_candidates(means_t, s_min, grid):
    """The v_th candidates by their definition: ``jnp.nanquantile`` of the
    tail with its zeros masked out (1.0 for a tail with no positive value)."""
    tail = means_t[s_min:]
    qs = jnp.linspace(grid.v_quantile_lo, grid.v_quantile_hi, grid.n_v)
    cand = jnp.nanquantile(jnp.where(tail > 0, tail, jnp.nan), qs)
    return jnp.maximum(jnp.where(jnp.isnan(cand), 1.0, cand), 1e-6)


def _tail_means(case: str, d: int, k: int, s_min: int) -> np.ndarray:
    rng = np.random.default_rng(11)
    m = rng.random((d, k)).astype(np.float32)
    if case == "sparse":        # most tail values zero, the rest distinct
        m[s_min:] *= rng.random((d - s_min, k)) < 0.15
    elif case == "ties":        # five values, so order statistics repeat
        m[s_min:] = rng.choice(np.float32([0, 0.25, 0.5, 0.75, 1.0]),
                               (d - s_min, k))
    elif case == "one_positive":
        m[s_min:] = 0
        m[d - 1, k - 1] = 0.375
    elif case == "zero_tail":   # degenerate: every candidate is 1.0
        m[s_min:] = 0
    return m


@pytest.mark.parametrize("grid", [EstGrid(), EstGrid(n_v=5),
                                  EstGrid(n_v=7, v_quantile_lo=0.0,
                                          v_quantile_hi=1.0)],
                         ids=["n_v24", "n_v5", "n_v7_full_range"])
@pytest.mark.parametrize("case", ["sparse", "ties", "one_positive",
                                  "zero_tail"])
def test_v_candidates_are_nanquantiles_bit_for_bit(case, grid):
    """The counting bisection gives ``nanquantile``'s candidates bit for bit,
    on one device and with K sharded over four (positive counts here are
    far below 2**24, where ``nanquantile``'s float32 count is exact)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_test_mesh

    d, k = 200, 16
    s_min = int(grid.s_min_frac * d)
    means = jnp.asarray(_tail_means(case, d, k, s_min))
    want = np.asarray(jax.jit(_nanquantile_candidates,
                              static_argnums=(1, 2))(means, s_min, grid))
    mesh = make_test_mesh((1, 4), ("data", "model"))
    sharded = jax.device_put(means, NamedSharding(mesh, P(None, "model")))
    for got in (_v_candidates(means, s_min, grid),
                _v_candidates(sharded, s_min, grid)):
        got = np.asarray(got)
        assert got.shape == (grid.n_v,)
        np.testing.assert_array_equal(got.view(np.int32),
                                      want.view(np.int32))

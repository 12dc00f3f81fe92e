"""EstParams — structural-parameter estimation (paper §V, App. B–C, Alg. 7).

Minimises J(s', v_h) = φ1 + φ2 + φ̃3, the approximate number of multiply-adds:

    φ1(s')    = Σ_{s<s'} df_s·mf_s                       (Region-1 exact cost)
    φ2(s',h)  = Σ_{s≥s'} df_s·(mfH)_{s,h}                (Region-2 exact cost)
    φ̃3(s',h)  = Σ_i (ntH)_{i,s'} · (K/e)^{Δρ̄/(ρ_a−ρ̄_i)}  (expected verify cost,
                exponential-family model of the similarity distribution,
                Eqs. 10–13 / 23–31)

with Δρ̄(i,s',h) = Σ_{p: id_p ≥ s'} u_p · Δv̄_{id_p,h} and
Δv̄_{s,h} = (1/K) Σ_k relu(v_h − v_{s,k})  (Eq. 39, counting absent centroids).

Hardware adaptation: the paper evaluates all s' via a descending recurrence
over a partial *object*-inverted index — a CPU-AFM trick to touch each
posting once.  On TPU the architecture-friendly evaluation is a dense grid:
suffix-sums over each object's (df-sorted) tuple positions give Δρ̄ for every
s' candidate in one vectorised pass, chunked over objects.  Same objective,
same minimiser; DESIGN.md §2 records the substitution.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.compat import shard_map
from repro.sparse import SparseDocs
from repro.core.meanindex import StructuralParams, delta_v_bar, mfh_table


@dataclasses.dataclass(frozen=True)
class EstGrid:
    n_v: int = 24            # |V^[th]| candidates
    n_s: int = 48            # t_th candidates
    s_min_frac: float = 0.80  # s_(min) = frac · D (paper: t_th lands near 0.9 D)
    v_quantile_lo: float = 0.50
    v_quantile_hi: float = 0.999
    chunk: int = 2048        # objects per φ̃3 chunk


def _k_axis(means_t) -> str | None:
    """The mesh axis the (D, K) means are sharded over along K, when that
    axis spans more than one device; None for means on one device."""
    sh = getattr(means_t, "sharding", None)
    if not isinstance(sh, NamedSharding):
        return None
    spec = tuple(sh.spec) + (None, None)
    axis = spec[1]
    if isinstance(axis, tuple):
        axis = axis[0] if len(axis) == 1 else None
    if spec[0] is not None or axis is None or sh.mesh.shape[axis] < 2:
        return None
    return axis


def _v_candidates(means_t: jax.Array, s_min: int, grid: EstGrid) -> jax.Array:
    """v_th candidates: ``grid.n_v`` quantiles of the positive tail-region
    values (rows ``s_min`` on), as ``jnp.nanquantile`` of the tail with its
    zeros masked out gives them; 1.0 for a tail with no positive value."""
    axis = _k_axis(means_t)
    mesh = None if axis is None else means_t.sharding.mesh
    return _v_candidates_fn(mesh, axis, s_min, grid)(means_t)


@functools.lru_cache(maxsize=None)
def _v_candidates_fn(mesh, axis: str | None, s_min: int, grid: EstGrid):
    """The program of :func:`_v_candidates`, one per geometry.  It neither
    sorts the tail nor, for means sharded over ``axis`` along K, gathers it
    onto one device: each quantile's two order statistics are found by
    bisection over the float32 bit patterns (positive floats order as
    their bits), each step counting the positive tail values at or below a
    probe on every device and summing the counts over ``axis``.  The
    quantiles are ``nanquantile``'s linear interpolation between those
    order statistics, with the positive values counted exactly in int32
    (``nanquantile`` counts them in float32, exact below 2**24)."""
    total = (lambda x: x) if axis is None else partial(lax.psum,
                                                       axis_name=axis)

    def tail_quantiles(means_t):
        tail = means_t[s_min:]
        pos = tail > 0
        v = jnp.where(pos, tail, jnp.inf)
        n = total(jnp.sum(pos, dtype=jnp.int32))
        qs = jnp.linspace(grid.v_quantile_lo, grid.v_quantile_hi, grid.n_v)
        at = qs * (n - 1).astype(jnp.float32)
        lo, hi = jnp.floor(at), jnp.ceil(at)
        w = at - lo
        ranks = jnp.clip(jnp.concatenate([lo, hi]).astype(jnp.int32), 0,
                         jnp.maximum(n - 1, 0))

        def bisect(_, bounds):
            below, above = bounds            # the order statistic's bits
            mid = below + (above - below) // 2
            x = lax.bitcast_convert_type(mid, jnp.float32)
            count = total(jnp.sum(v[:, :, None] <= x, axis=(0, 1),
                                  dtype=jnp.int32))
            ok = count > ranks
            return jnp.where(ok, below, mid + 1), jnp.where(ok, mid, above)

        inf_bits = jnp.full_like(ranks, 0x7F800000)
        bits, _ = lax.fori_loop(0, 31, bisect,
                                (jnp.zeros_like(ranks), inf_bits))
        x = lax.bitcast_convert_type(bits, jnp.float32)
        cand = x[:grid.n_v] * (1.0 - w) + x[grid.n_v:] * w
        cand = jnp.where(n > 0, cand, 1.0)   # degenerate tail -> vacuous
        return jnp.maximum(cand, 1e-6)

    if axis is None:
        return jax.jit(tail_quantiles)
    return jax.jit(shard_map(tail_quantiles, mesh=mesh, in_specs=P(None, axis),
                             out_specs=P()))


@partial(jax.jit, static_argnames=("k",))
def _phi3_chunk(ids, vals, nnz, dvbar, colsum, rho_a, s_grid, *, k: int):
    """φ̃3 contribution of one object chunk → (S', H)."""
    c, p = ids.shape
    h = dvbar.shape[1]
    live = jnp.arange(p)[None, :] < nnz[:, None]
    u = jnp.where(live, vals, 0.0)

    w = u[:, :, None] * dvbar[ids]                      # (C, P, H)
    w = jnp.where(live[:, :, None], w, 0.0)
    suf = jnp.flip(jnp.cumsum(jnp.flip(w, 1), axis=1), 1)  # suffix sums
    suf = jnp.concatenate([suf, jnp.zeros((c, 1, h))], axis=1)

    rho_bar = jnp.sum(u * colsum[ids], axis=1) / k      # Eq. 32
    denom = jnp.maximum(rho_a - rho_bar, 1e-9)          # ρ_a(i) − ρ̄_i

    # p* = first tuple position with id >= s'  (ids ascend within a row)
    pstar = jnp.sum(live[:, :, None] & (ids[:, :, None] < s_grid[None, None, :]),
                    axis=1)                              # (C, S')
    nt_h = (nnz[:, None] - pstar).astype(jnp.float32)    # (ntH)_{i,s'}

    dr = jnp.take_along_axis(suf, pstar[:, :, None], axis=1)  # (C, S', H)
    x = dr / denom[:, None, None]
    log_ke = jnp.log(k / jnp.e)
    factor = jnp.minimum(jnp.exp(x * log_ke), float(k))  # K·Prob ≤ K
    return jnp.sum(nt_h[:, :, None] * factor, axis=0)    # (S', H)


def _est_tables(df: jax.Array, means_t: jax.Array, grid: EstGrid):
    """The corpus-independent half of Alg. 7: candidate grids + φ1/φ2 from
    the df/mean statistics, and the per-term tables φ̃3 consumes."""
    d = means_t.shape[0]
    s_min = int(grid.s_min_frac * d)
    s_grid = jnp.unique(jnp.linspace(s_min, d, grid.n_s).astype(jnp.int32))
    v_grid = _v_candidates(means_t, s_min, grid)

    mf = jnp.sum(means_t > 0, axis=1).astype(jnp.float32)
    dff = df.astype(jnp.float32)

    # φ1: prefix sums of df·mf
    c1 = jnp.concatenate([jnp.zeros((1,)), jnp.cumsum(dff * mf)])
    phi1 = c1[s_grid]                                      # (S',)

    # φ2: suffix sums of df·mfH per candidate v_h
    mfh = mfh_table(means_t, v_grid).astype(jnp.float32)   # (D, H)
    sfx = jnp.flip(jnp.cumsum(jnp.flip(dff[:, None] * mfh, 0), axis=0), 0)
    sfx = jnp.concatenate([sfx, jnp.zeros((1, len(v_grid)))], axis=0)
    phi2 = sfx[s_grid]                                     # (S', H)

    dvbar = delta_v_bar(means_t, v_grid)                   # (D, H)
    colsum = jnp.sum(means_t, axis=1)                      # (D,)
    return s_grid, v_grid, phi1, phi2, dvbar, colsum


def _est_minimize(s_grid, v_grid, phi1, phi2, phi3):
    j_table = phi1[:, None] + phi2 + phi3
    flat = int(jnp.argmin(j_table))
    si, hi = np.unravel_index(flat, j_table.shape)
    params = StructuralParams(t_th=s_grid[si].astype(jnp.int32),
                              v_th=v_grid[hi].astype(jnp.float32))
    aux = {"J": j_table, "s_grid": s_grid, "v_grid": v_grid,
           "phi1": phi1, "phi2": phi2, "phi3": phi3}
    return params, aux


def estimate_params(docs: SparseDocs, df: jax.Array, means_t: jax.Array,
                    rho_self: jax.Array, *, k: int,
                    grid: EstGrid = EstGrid()) -> tuple[StructuralParams, dict]:
    """Returns the minimising (t_th, v_th) and an aux dict with the J table.

    rho_self: (N,) ρ_{a(i)} against the current means — the update step's
    refreshed self-similarities (Alg. 6), exactly what Alg. 7 consumes.
    Runs in the span ``repro.estparams``, host syncs included.
    """
    with obs.span("estparams"):
        s_grid, v_grid, phi1, phi2, dvbar, colsum = _est_tables(df, means_t,
                                                                grid)

        # φ̃3: chunked over objects
        n = docs.n_docs
        phi3 = jnp.zeros((len(s_grid), len(v_grid)))
        for start in range(0, n, grid.chunk):
            end = min(start + grid.chunk, n)
            phi3 = phi3 + _phi3_chunk(docs.ids[start:end],
                                      docs.vals[start:end],
                                      docs.nnz[start:end], dvbar, colsum,
                                      rho_self[start:end], s_grid, k=k)

        return _est_minimize(s_grid, v_grid, phi1, phi2, phi3)


def estimate_params_store(store, df: jax.Array, means_t: jax.Array,
                          rho_self: jax.Array, *, k: int,
                          grid: EstGrid = EstGrid()):
    """Alg. 7 over an out-of-core :class:`repro.sparse.DocStore`.

    φ1/φ2 need only the df/mean statistics; φ̃3 — already an object-chunked
    sum in the resident path — accumulates store chunk by store chunk, so
    the estimate uses the ENTIRE corpus without it ever being resident.
    Dead tail rows contribute exactly 0 (no live tuples ⇒ zero suffix sums
    and (ntH) = 0), so whole chunks are fed as-is.  A one-chunk store
    reproduces :func:`estimate_params` on the resident corpus bit for bit.

    rho_self: (store.n_rows,) — the streaming fit's refreshed ρ, pad rows
    at the 0 convention.
    """
    s_grid, v_grid, phi1, phi2, dvbar, colsum = _est_tables(df, means_t, grid)

    c = store.chunk_size
    phi3 = jnp.zeros((len(s_grid), len(v_grid)))
    for ci in range(store.n_chunks):
        cdocs = store.chunk(ci)
        rho_c = rho_self[ci * c:(ci + 1) * c]
        for start in range(0, c, grid.chunk):
            end = min(start + grid.chunk, c)
            phi3 = phi3 + _phi3_chunk(cdocs.ids[start:end],
                                      cdocs.vals[start:end],
                                      cdocs.nnz[start:end], dvbar, colsum,
                                      rho_c[start:end], s_grid, k=k)

    return _est_minimize(s_grid, v_grid, phi1, phi2, phi3)

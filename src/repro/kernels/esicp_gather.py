"""Pallas kernel: fused ES-filter gathering phase (paper Alg. 3 / G_0, G_1).

One pass over the mean-inverted index producing, per (object, centroid):

    rho12[b,k] = Σ_{s<t_th} u·v  +  Σ_{s≥t_th, v≥v_th} u·v     (exact part)
    y[b,k]     = Σ_{s≥t_th, v<v_th} u                          (Region-3 mass)

The three-region classification is two uniform masks over the means block —
the shared (t_th, v_th) thresholds are scalar-prefetch operands living in
SMEM, so the kernel body has no data-dependent branches at all (the paper's
AFM requirement, realised as TPU select lanes).

Everything the ES assignment step needs comes off ONE densified slab per
(B, D) block (kernel engine v2, see sparse_sim.py for the grid order,
occupancy pruning and head-cache mechanics):

  * rho12 and y — the bound operands (always);
  * ``with_sims`` — the full exact similarity ``slab @ means`` as a third
    accumulator, deleting the separate ``sparse_sim`` launch the backend
    used to pay per batch;
  * ``diag`` — the fused Mult count over the *exact region*
    (``nz & where(tail, v ≥ v_th, True)``), off the live-count twin slab,
    deleting the binarised side-launches and the host-side (D, K) region
    mask they needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_sim import (_COMPILER_PARAMS, _HIGHEST, _head_index,
                                      _slab, _tile_spec, tiles_t)


def _gather_kernel(occ_ref, scalars_ref, *refs, d_blk: int, nd: int,
                   n_head: int, with_sims: bool, diag: bool):
    ins = 3 + (1 if n_head else 0) + (1 if n_head and diag else 0)
    ids_ref, vals_ref, means_ref = refs[0], refs[1], refs[2]
    head_ref = refs[3] if n_head else None
    headc_ref = refs[4] if n_head and diag else None
    outs = refs[ins:]
    rho_ref, y_ref = outs[0], outs[1]
    sims_ref = outs[2] if with_sims else None
    cnt_ref = outs[-1] if diag else None

    i = pl.program_id(0)
    l = pl.program_id(2)
    d0 = l * d_blk
    t_th = scalars_ref[0]
    v_th = scalars_ref[1]

    @pl.when(l == 0)
    def _init():
        rho_ref[...] = jnp.zeros_like(rho_ref)
        y_ref[...] = jnp.zeros_like(y_ref)
        if with_sims:
            sims_ref[...] = jnp.zeros_like(sims_ref)
        if diag:
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when(occ_ref[i, l] != 0)
    def _work():
        means = means_ref[...]                               # (D_blk, K_sup)
        term = d0 + jax.lax.broadcasted_iota(jnp.int32, means.shape, 0)
        tail = (term.astype(jnp.float32) >= t_th)
        # Region masks as plain compares joined by and/or: Mosaic cannot
        # select between boolean vectors (no i8 -> i1 truncation).
        exact = ~tail | (means >= v_th)          # Regions 1 and 2
        region3 = tail & (means < v_th)

        if diag:
            slab, cslab = _slab(ids_ref, vals_ref, head_ref, headc_ref, l,
                                d_blk=d_blk, nd=nd, n_head=n_head, diag=True)
            w_cnt = jnp.where((means > 0) & exact, 1.0, 0.0)
            cnt_ref[...] += jnp.dot(cslab, w_cnt,
                                    preferred_element_type=jnp.float32,
                                    precision=_HIGHEST)
        else:
            slab = _slab(ids_ref, vals_ref, head_ref, headc_ref, l,
                         d_blk=d_blk, nd=nd, n_head=n_head, diag=False)

        rho_ref[...] += jnp.dot(slab, jnp.where(exact, means, 0.0),
                                preferred_element_type=jnp.float32,
                                precision=_HIGHEST)
        y_ref[...] += jnp.dot(slab, jnp.where(region3, 1.0, 0.0),
                              preferred_element_type=jnp.float32,
                              precision=_HIGHEST)
        if with_sims:
            sims_ref[...] += jnp.dot(slab, means,
                                     preferred_element_type=jnp.float32,
                                     precision=_HIGHEST)


def esicp_gather_pallas(ids, vals, means_t, t_th, v_th, occ, head=None,
                        headc=None, *, b_blk: int = 128, k_sup: int = 128,
                        d_blk: int = 256, n_head: int = 0,
                        with_sims: bool = False, diag: bool = False,
                        interpret: bool = False):
    """Returns (rho12, y[, sims][, counts]), each (B, K) float32."""
    b, p = ids.shape
    d, k = means_t.shape
    nd = d // d_blk
    assert b % b_blk == 0 and k % k_sup == 0 and d % d_blk == 0 and p % 8 == 0
    assert occ.shape == (b // b_blk, nd)
    grid = (b // b_blk, k // k_sup, nd)
    scalars = jnp.stack([jnp.asarray(t_th, jnp.float32),
                         jnp.asarray(v_th, jnp.float32)])

    tile = lambda i, j, l, occ: i
    in_specs = [
        pl.BlockSpec((2,), lambda i, j, l, occ: (0,)),   # shared thresholds
        _tile_spec(p, b_blk, tile),
        _tile_spec(p, b_blk, tile),
        pl.BlockSpec((d_blk, k_sup), lambda i, j, l, occ: (l, j)),
    ]
    inputs = [scalars, tiles_t(ids, b_blk), tiles_t(vals, b_blk), means_t]
    if n_head:
        in_specs.append(pl.BlockSpec((b_blk, d_blk), _head_index(nd, n_head)))
        inputs.append(head)
        if diag:
            in_specs.append(pl.BlockSpec((b_blk, d_blk),
                                         _head_index(nd, n_head)))
            inputs.append(headc)
    n_out = 2 + int(with_sims) + int(diag)
    out_specs = [pl.BlockSpec((b_blk, k_sup), lambda i, j, l, occ: (i, j))
                 for _ in range(n_out)]
    out_shape = [jax.ShapeDtypeStruct((b, k), jnp.float32)
                 for _ in range(n_out)]

    out = pl.pallas_call(
        functools.partial(_gather_kernel, d_blk=d_blk, nd=nd, n_head=n_head,
                          with_sims=with_sims, diag=diag),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shape,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(occ, *inputs)
    return tuple(out)

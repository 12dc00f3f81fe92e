"""Pallas kernel: update-step scatter-add (paper Alg. 6 lines 2–5) as MXU work.

λ[k, d] += Σ_b onehot(assign[b] == k) · x[b, d] — the cluster-sum accumulation
expressed as two one-hot densifications feeding a single matmul:

    grid = (K superblocks, D tiles, B tiles)     # B sequential → accumulate
    slab   = densify(ids, vals)                   (B_blk, D_blk)
    sel    = onehot(assign − k0)                  (B_blk, K_sup)
    out   += selᵀ @ slab                          (MXU)

A CPU implementation scatters; a TPU implementation must not (serialised
HBM read-modify-write) — this is the update-step half of the AFM adaptation.

Kernel engine v2 (see sparse_sim.py): K rides in ``k_sup``-wide superblocks
so the slab is built once per (B, D) block, not once per (K, D, B) step;
the occupancy map skips empty (B-tile, D-block) cells; the trailing high-df
blocks read the cached head slab instead of re-densifying.  Rows whose
``sel`` column is out of range contribute zero whatever the slab holds, so
cached slabs stay exact under the shard-local masking conventions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_sim import (_COMPILER_PARAMS, _HIGHEST, _slab,
                                      _tile_spec, tiles_t)


def _update_kernel(occ_ref, *refs, d_blk: int, k_sup: int, nd: int,
                   n_head: int, has_init: bool):
    ins = 3 + (1 if n_head else 0) + (1 if has_init else 0)
    assign_ref, ids_ref, vals_ref = refs[0], refs[1], refs[2]
    head_ref = refs[3] if n_head else None
    init_ref = refs[ins - 1] if has_init else None
    out_ref = refs[ins]

    j = pl.program_id(0)
    l = pl.program_id(1)
    m = pl.program_id(2)
    k0 = j * k_sup

    @pl.when(m == 0)
    def _init():
        if has_init:
            out_ref[...] = init_ref[...]
        else:
            out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(occ_ref[m, l] != 0)
    def _work():
        slab = _slab(ids_ref, vals_ref, head_ref, None, l, d_blk=d_blk,
                     nd=nd, n_head=n_head, diag=False)
        local = assign_ref[...][:, 0] - k0                    # (B_blk,)
        iota = jax.lax.broadcasted_iota(jnp.int32,
                                        (local.shape[0], k_sup), 1)
        sel = (local[:, None] == iota).astype(jnp.float32)    # (B_blk, K_sup)
        out_ref[...] += jnp.dot(sel.T, slab,
                                preferred_element_type=jnp.float32,
                                precision=_HIGHEST)


def segment_update_pallas(assign, ids, vals, k: int, d: int, occ,
                          head=None, init=None, *, b_blk: int = 128,
                          k_sup: int = 128, d_blk: int = 256,
                          n_head: int = 0, interpret: bool = False):
    """assign: (B,) int32; ids/vals: (B, P). Returns (K, D) float32 sums.

    ``init`` (K, D) seeds the accumulator in place of zeros (aliased to the
    output), so a row-chunked caller continues the one sequential sweep's
    summation order exactly."""
    b, p = ids.shape
    nd = d // d_blk
    assert b % b_blk == 0 and k % k_sup == 0 and d % d_blk == 0 and p % 8 == 0
    assert occ.shape == (b // b_blk, nd)
    grid = (k // k_sup, nd, b // b_blk)

    def head_idx(j, l, m, occ):
        return (m, jnp.maximum(l - (nd - n_head), 0))

    tile = lambda j, l, m, occ: m
    in_specs = [
        pl.BlockSpec((b_blk, 1), lambda j, l, m, occ: (m, 0)),
        _tile_spec(p, b_blk, tile),
        _tile_spec(p, b_blk, tile),
    ]
    inputs = [assign[:, None], tiles_t(ids, b_blk), tiles_t(vals, b_blk)]
    if n_head:
        in_specs.append(pl.BlockSpec((b_blk, d_blk), head_idx))
        inputs.append(head)
    aliases = {}
    if init is not None:
        in_specs.append(pl.BlockSpec((k_sup, d_blk),
                                     lambda j, l, m, occ: (j, l)))
        inputs.append(init)
        aliases = {len(inputs): 0}     # operand 0 is the occupancy map

    return pl.pallas_call(
        functools.partial(_update_kernel, d_blk=d_blk, k_sup=k_sup, nd=nd,
                          n_head=n_head, has_init=init is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=pl.BlockSpec((k_sup, d_blk),
                                   lambda j, l, m, occ: (j, l))),
        out_shape=jax.ShapeDtypeStruct((k, d), jnp.float32),
        input_output_aliases=aliases,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(occ, *inputs)

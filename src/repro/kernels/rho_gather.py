"""Pallas kernel: ρ_self refresh — per-object gather of its own centroid.

The update step's lines 6–7 (Alg. 6) compute ρ_{a(i)} = x_i · μ_{a(i)} for
every object against its *new* centroid.  A CPU implementation gathers the
assigned column per object tuple; on TPU a data-dependent column gather from
``means_t (D, K)`` would serialise, so the gather is expressed as a one-hot
matmul over the centroid tile — the ρ_self half of the AFM update adaptation
(the scatter half is :mod:`repro.kernels.segment_update`):

    grid = (B tiles, K superblocks, D tiles)     # K, D sequential → accumulate
    slab     = densify(ids, vals)                 (B_blk, D_blk)
    sel      = onehot(assign − k0)                (B_blk, K_sup)
    gathered = sel @ means_blkᵀ                   (MXU)  — own-centroid columns
    out[b]  += Σ_d slab[b, d] · gathered[b, d]    (VPU row reduce)

The output rides a 128-lane block (every lane carries the same partial) so
the (B,) result stays tile-aligned; the wrapper slices lane 0.

Kernel engine v2 (see sparse_sim.py): K rides in ``k_sup``-wide superblocks
(densify once per (B, D) block), the occupancy map skips empty cells, and
the trailing high-df blocks read the cached head slab.  Out-of-range
assignments still select no centroid column, so cached slabs are inert for
masked rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_sim import (_COMPILER_PARAMS, _HIGHEST, _head_index,
                                      _slab, _tile_spec, tiles_t)


def _rho_kernel(occ_ref, *refs, d_blk: int, k_sup: int, nd: int, n_head: int):
    ins = 4 + (1 if n_head else 0)
    assign_ref, ids_ref, vals_ref, means_ref = refs[:4]
    head_ref = refs[4] if n_head else None
    out_ref = refs[ins]

    i = pl.program_id(0)
    j = pl.program_id(1)
    l = pl.program_id(2)
    k0 = j * k_sup

    @pl.when((j == 0) & (l == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(occ_ref[i, l] != 0)
    def _work():
        slab = _slab(ids_ref, vals_ref, head_ref, None, l, d_blk=d_blk,
                     nd=nd, n_head=n_head, diag=False)
        local = assign_ref[...][:, 0] - k0                    # (B_blk,)
        iota = jax.lax.broadcasted_iota(jnp.int32,
                                        (local.shape[0], k_sup), 1)
        sel = (local[:, None] == iota).astype(jnp.float32)    # (B_blk, K_sup)
        gathered = jnp.dot(sel, means_ref[...].T,
                           preferred_element_type=jnp.float32,
                           precision=_HIGHEST)  # (B_blk, D_blk)
        part = jnp.sum(slab * gathered, axis=1, keepdims=True)  # (B_blk, 1)
        out_ref[...] += jnp.broadcast_to(part, (part.shape[0], 128))


def rho_gather_pallas(assign, ids, vals, means_t, occ, head=None, *,
                      b_blk: int = 128, k_sup: int = 128, d_blk: int = 256,
                      n_head: int = 0, interpret: bool = False):
    """assign: (B,) int32; ids/vals: (B, P); means_t: (D, K). -> (B,) float32.

    Out-of-range assignments (padding rows use ``assign = K``) select no
    centroid column and produce ρ = 0.
    """
    b, p = ids.shape
    d, k = means_t.shape
    nd = d // d_blk
    assert b % b_blk == 0 and k % k_sup == 0 and d % d_blk == 0 and p % 8 == 0
    assert occ.shape == (b // b_blk, nd)
    grid = (b // b_blk, k // k_sup, nd)

    tile = lambda i, j, l, occ: i
    in_specs = [
        pl.BlockSpec((b_blk, 1), lambda i, j, l, occ: (i, 0)),
        _tile_spec(p, b_blk, tile),
        _tile_spec(p, b_blk, tile),
        pl.BlockSpec((d_blk, k_sup), lambda i, j, l, occ: (l, j)),
    ]
    inputs = [assign[:, None], tiles_t(ids, b_blk), tiles_t(vals, b_blk),
              means_t]
    if n_head:
        in_specs.append(pl.BlockSpec((b_blk, d_blk), _head_index(nd, n_head)))
        inputs.append(head)

    out = pl.pallas_call(
        functools.partial(_rho_kernel, d_blk=d_blk, k_sup=k_sup, nd=nd,
                          n_head=n_head),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=pl.BlockSpec((b_blk, 128),
                                   lambda i, j, l, occ: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((b, 128), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(occ, *inputs)
    return out[:, 0]

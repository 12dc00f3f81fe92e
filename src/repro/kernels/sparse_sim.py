"""Pallas kernel: sparse-object × dense-mean-block similarity.

TPU adaptation of the paper's TAAT inner loop (Alg. 1 lines 3–5).  The CPU
algorithm chases posting lists; on TPU we instead *densify* each object tile
into a (B_blk, D_blk) slab — one D-block at a time, exploiting the df-sorted
term layout — and feed the MXU:

    grid = (B tiles, K superblocks, D tiles)     # D sequential → accumulate
    slab[b, d]  = Σ_p vals[b,p] · [ids[b,p] == d0+d]      (VPU one-hot build)
    out[b, k]  += slab @ means_blk                         (MXU matmul)

Skew-aware engine (v2):

* **Slab reuse across K.**  K rides in ``k_sup``-wide superblocks (the whole
  padded K when it fits the VMEM budget), so the expensive densification
  runs once per (B, D) block instead of once per (B, K, D) step — a
  K/k_blk× cut in one-hot work.  The D loop stays innermost: each output
  block is revisited only on consecutive grid steps, the safe accumulation
  pattern.
* **Occupancy pruning.**  A scalar-prefetch (SMEM) map says which
  (B-tile, D-block) cells hold live tuples; empty cells — most of the
  low-df range, by Zipf skew — skip densify and matmul entirely.  Exact:
  an empty cell's slab is all zeros.
* **Cached head slabs.**  The trailing high-df blocks can arrive
  pre-densified (``kernels/plan.py``); the kernel reads the cached slab
  instead of rebuilding it every epoch.
* **Fused Mult diagnostics.**  With ``diag`` the kernel carries a second
  accumulator ``counts[b,k] = Σ_p live[b,p]·[means[ids[b,p],k] > 0]`` —
  the paper's visited-pair count — off the same one-hot walk
  (``_densify(counts=True)``), so diagnostics cost no extra kernel launch.

The one-hot densification is the paper's inverted-index walk with the
branch-misprediction hazard replaced by uniform lane masks — the AFM
translation from DESIGN.md §2/§3.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Every f32 matmul of the clustering kernels contracts at full f32: Mosaic's
# default may round the operands to bf16, which the exactness contract
# (DESIGN.md §5) cannot absorb.
_HIGHEST = jax.lax.Precision.HIGHEST

# Scoped-VMEM ceiling of the clustering kernels.  Mosaic's 16 MiB default
# is exceeded by the fused ES gather at the widest K superblock (four
# (b_blk, k_sup) f32 accumulators plus the (d_blk, k_sup) means block,
# double-buffered: 16.38 MiB at k_sup=1024); every TPU from v4 on has at
# least 32 MiB of VMEM.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 << 20)


def tiles_t(x, b_blk: int):
    """(B, P) row operand -> (B/b_blk, P, b_blk): each row tile with its
    tuple slots on the sublane axis, the layout the densify walk reads."""
    b, p = x.shape
    return x.reshape(b // b_blk, b_blk, p).transpose(0, 2, 1)


def _tile_spec(p: int, b_blk: int, tile):
    """BlockSpec of a ``tiles_t`` operand; ``tile`` maps grid indices to the
    row tile."""
    return pl.BlockSpec((None, p, b_blk), lambda *g: (tile(*g), 0, 0))


def _densify(chunk, n_chunks: int, b: int, d0, d_blk: int,
             counts: bool = False):
    """A row tile's tuples -> its (b, D_blk) dense slab for terms
    [d0, d0+d_blk) — with ``counts``, also the live-count slab (1.0 per live
    slot), the operand of the fused Mult accumulator, off the same walk.

    ``chunk(c)`` returns slots [8c, 8c+8) as (8, b) ids and values: slots
    ride the sublane axis, so a chunk is an aligned dynamic row slice, which
    Mosaic lowers, and the loop body (eight slots) does not grow with P.
    Each chunk is transposed to (b, 8) so a slot is a column that
    broadcasts along the slab's lanes.  No matmul touches the values (no
    MXU rounding); term ids are distinct within a row, so each slab cell
    receives at most one nonzero and the slab is exact whatever the order
    of the slots."""
    term = d0 + jax.lax.broadcasted_iota(jnp.int32, (b, d_blk), 1)

    def body(c, accs):
        ids8, vals8 = chunk(c)
        ids8, vals8 = ids8.T, vals8.T
        live8 = jnp.where(vals8 != 0.0, 1.0, 0.0)
        for s in range(8):
            hit = ids8[:, s:s + 1] == term
            accs = (accs[0] + jnp.where(hit, vals8[:, s:s + 1], 0.0),) + (
                (accs[1] + jnp.where(hit, live8[:, s:s + 1], 0.0),)
                if counts else ())
        return accs

    zero = jnp.zeros((b, d_blk), jnp.float32)
    accs = jax.lax.fori_loop(0, n_chunks, body,
                             (zero, zero) if counts else (zero,))
    return accs if counts else accs[0]


def densify_rows(ids, vals, d0, d_blk: int, counts: bool = False):
    """The kernels' densify walk over plain (B, P) arrays (P a multiple of
    8): how the cached head slabs are built."""
    ids_t, vals_t = ids.T, vals.T
    rows = lambda x, c: jax.lax.dynamic_slice_in_dim(x, c * 8, 8, 0)
    return _densify(lambda c: (rows(ids_t, c), rows(vals_t, c)),
                    ids.shape[1] // 8, ids.shape[0], d0, d_blk, counts)


def _slab(ids_ref, vals_ref, head_ref, headc_ref, l, *, d_blk, nd, n_head,
          diag):
    """The (B_blk, D_blk) slab(s) for D-block ``l``: cached for the trailing
    high-df blocks, densified otherwise.  ``ids_ref``/``vals_ref`` hold one
    ``tiles_t`` tile, (P, B_blk)."""
    p, b = ids_ref.shape

    def chunk(c):
        rows = pl.ds(pl.multiple_of(c * 8, 8), 8)
        return ids_ref[rows, :], vals_ref[rows, :]

    build = lambda: _densify(chunk, p // 8, b, l * d_blk, d_blk,
                             counts=diag)
    if n_head == 0:
        return build()
    cached = ((lambda: (head_ref[...], headc_ref[...])) if diag
              else (lambda: head_ref[...]))
    return jax.lax.cond(l >= nd - n_head, cached, build)


def _head_index(nd: int, n_head: int):
    """Index map for the cached-head operand: clamped so pre-head D steps
    keep pointing at block 0 (an unchanged index between consecutive grid
    steps costs no re-fetch)."""
    return lambda i, j, l, occ: (i, jnp.maximum(l - (nd - n_head), 0))


def _sim_kernel(occ_ref, *refs, d_blk: int, nd: int, n_head: int, diag: bool):
    ins = 2 + 1 + (1 if n_head else 0) + (1 if n_head and diag else 0)
    ids_ref, vals_ref, means_ref = refs[0], refs[1], refs[2]
    head_ref = refs[3] if n_head else None
    headc_ref = refs[4] if n_head and diag else None
    out_ref = refs[ins]
    cnt_ref = refs[ins + 1] if diag else None

    i = pl.program_id(0)
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        if diag:
            cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when(occ_ref[i, l] != 0)
    def _work():
        means = means_ref[...]
        if diag:
            slab, cslab = _slab(ids_ref, vals_ref, head_ref, headc_ref, l,
                                d_blk=d_blk, nd=nd, n_head=n_head, diag=True)
            cnt_ref[...] += jnp.dot(cslab, (means > 0).astype(jnp.float32),
                                    preferred_element_type=jnp.float32,
                                    precision=_HIGHEST)
        else:
            slab = _slab(ids_ref, vals_ref, head_ref, headc_ref, l,
                         d_blk=d_blk, nd=nd, n_head=n_head, diag=False)
        out_ref[...] += jnp.dot(slab, means,
                                preferred_element_type=jnp.float32,
                                precision=_HIGHEST)


def sparse_sim_pallas(ids, vals, means_t, occ, head=None, headc=None, *,
                      b_blk: int = 128, k_sup: int = 128, d_blk: int = 256,
                      n_head: int = 0, diag: bool = False,
                      interpret: bool = False):
    """ids/vals: (B, P) padded sparse objects; means_t: (D, K); occ: the
    (B//b_blk, D//d_blk) occupancy map.  -> (B, K) sims [, (B, K) counts].
    """
    b, p = ids.shape
    d, k = means_t.shape
    nd = d // d_blk
    assert b % b_blk == 0 and k % k_sup == 0 and d % d_blk == 0 and p % 8 == 0, (
        f"shapes must be block-aligned: B={b}/{b_blk} K={k}/{k_sup} "
        f"D={d}/{d_blk} P={p}/8")
    assert occ.shape == (b // b_blk, nd), (occ.shape, (b // b_blk, nd))
    grid = (b // b_blk, k // k_sup, nd)

    tile = lambda i, j, l, occ: i
    in_specs = [
        _tile_spec(p, b_blk, tile),
        _tile_spec(p, b_blk, tile),
        pl.BlockSpec((d_blk, k_sup), lambda i, j, l, occ: (l, j)),
    ]
    inputs = [tiles_t(ids, b_blk), tiles_t(vals, b_blk), means_t]
    if n_head:
        in_specs.append(pl.BlockSpec((b_blk, d_blk), _head_index(nd, n_head)))
        inputs.append(head)
        if diag:
            in_specs.append(pl.BlockSpec((b_blk, d_blk),
                                         _head_index(nd, n_head)))
            inputs.append(headc)
    out_specs = [pl.BlockSpec((b_blk, k_sup), lambda i, j, l, occ: (i, j))]
    out_shape = [jax.ShapeDtypeStruct((b, k), jnp.float32)]
    if diag:
        out_specs.append(pl.BlockSpec((b_blk, k_sup),
                                      lambda i, j, l, occ: (i, j)))
        out_shape.append(jax.ShapeDtypeStruct((b, k), jnp.float32))

    out = pl.pallas_call(
        functools.partial(_sim_kernel, d_blk=d_blk, nd=nd, n_head=n_head,
                          diag=diag),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shape,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(occ, *inputs)
    return tuple(out) if diag else out[0]

"""Prepared kernel plans: epoch-invariant densification cache + occupancy.

Documents are constant across Lloyd iterations, yet the Pallas kernels used
to rebuild every ``(B_blk, D_blk)`` one-hot slab from the raw tuples on
every call of every epoch.  A :class:`KernelPlan` captures the two facts
about a corpus (chunk) that cannot change during a fit:

* **occupancy** — which (B-tile, D-block) cells contain at least one live
  tuple.  Term ids are df-rank sorted (paper Table I), so Zipf skew
  concentrates the mass in the high-df trailing blocks and leaves most
  low-df cells empty; the kernels skip the densify + MXU work of an empty
  cell entirely.  The bookkeeping cost is one SMEM scalar read per grid
  step — far cheaper than the work it saves (the Schubert et al. bound
  discipline), and skipping is *exact*: an empty cell's slab is all zeros
  and contributes nothing to any accumulator, value or count.

* **head slabs** — the densified high-df head region.  Under ascending
  df-rank order the head of the Zipf distribution lives at the HIGHEST term
  ids, i.e. the trailing ``n_head`` D-blocks; nearly every tile visits them
  every epoch.  Caching their dense form once per chunk per fit is the TPU
  analogue of SIVF keeping the frequently-reused index region hot across
  iterations.  The cache holds the value slab *and* the live-count slab
  (both fall out of one one-hot walk, see ``_densify``) so the fused
  Mult diagnostics reuse it too.

Layout contract: plans are built against the *padded* geometry the kernel
wrappers produce — D rounded up to a ``d_blk`` multiple, rows padded to a
``tile_rows`` multiple and, within each tile, grouped into ``b_blk`` rows.
``occ`` therefore has one row per ``b_blk`` group *in tile order*, which is
exactly how a tiled epoch (``core/lloyd._fused_epoch``, the distributed
``lax.map`` chunking) slices it.  A wrapper that receives a plan whose
layout does not match the call falls back to inline occupancy (cheap) and
raw densification — plans are an optimisation, never a correctness input.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

DEFAULT_HEAD_BYTES = 32 << 20   # per-chunk budget for the cached head slabs

# The one source of truth for the clustering kernels' block geometry: the
# ops.py wrappers, the plan builders, and the distributed PlanMeta all
# derive their defaults from here, so a plan built with defaults always
# matches a call made with defaults.
DEFAULT_B_BLK = 128
DEFAULT_D_BLK = 256


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Epoch-invariant operands for the clustering kernels.

    occ:    (T, ND) int32 — nonzero where the (b_blk-group, D-block) cell
            holds at least one live tuple; None → wrappers compute inline.
    head:   (B, n_head·d_blk) float32 — densified trailing (high-df) blocks;
            None → kernels densify every block.
    headc:  (B, n_head·d_blk) float32 — live-count twin of ``head`` for the
            fused Mult accumulator; None when diagnostics are off.
    tuned:  optional :class:`repro.tune.config.TunedConfig` the plan was
            built for — the autotuner's winning knob vector, serialized
            alongside the occupancy maps so ``Backend.prepare`` reuses it
            across fits.  Rides the static aux data (it is hashable and
            changes the launch geometry, i.e. the trace).
    """

    occ: jax.Array | None
    head: jax.Array | None
    headc: jax.Array | None
    b_blk: int = DEFAULT_B_BLK
    d_blk: int = DEFAULT_D_BLK
    n_head: int = 0
    dim: int = 0
    tuned: object | None = None

    def tree_flatten(self):
        return ((self.occ, self.head, self.headc),
                (self.b_blk, self.d_blk, self.n_head, self.dim, self.tuned))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        occ, head, headc = leaves
        b_blk, d_blk, n_head, dim, tuned = aux
        return cls(occ=occ, head=head, headc=headc, b_blk=b_blk,
                   d_blk=d_blk, n_head=n_head, dim=dim, tuned=tuned)

    def without_occ(self) -> "KernelPlan":
        """Drop the occupancy map (kept: head cache).  Used when the call's
        row grouping differs from the plan's tile layout — e.g. the resident
        update phase runs over the whole corpus while the plan's occ was
        grouped per epoch tile; inline occupancy is recomputed instead."""
        return dataclasses.replace(self, occ=None)

    def without_head(self) -> "KernelPlan":
        """Drop the cached slabs (kept: occupancy).  Used for calls whose
        value operands differ from the raw tuples the cache was built from
        (e.g. the CS head/tail partial passes)."""
        return dataclasses.replace(self, head=None, headc=None, n_head=0)

    def slice_rows(self, n: int) -> "KernelPlan":
        """First ``n`` rows of the cached slabs, occupancy dropped — for
        calls on a row prefix of the plan's corpus (ρ_self refresh over an
        unpadded chunk)."""
        return dataclasses.replace(
            self, occ=None,
            head=None if self.head is None else self.head[:n],
            headc=None if self.headc is None else self.headc[:n])


def _pad_rows(x, multiple: int):
    rem = (-x.shape[0]) % multiple
    if rem == 0:
        return x
    return jnp.pad(x, ((0, rem),) + ((0, 0),) * (x.ndim - 1))


def occupancy_map(ids, vals, *, dim: int, b_blk: int = DEFAULT_B_BLK,
                  d_blk: int = DEFAULT_D_BLK,
                  tile_rows: int | None = None):
    """(T, ND) int32 live-cell map over ``b_blk`` row groups × D-blocks.

    Rows are first padded to a ``tile_rows`` multiple (dead rows are never
    occupied), then each tile is independently grouped into ``b_blk`` rows —
    the grouping a tiled caller's per-tile kernel launches will use.  With
    ``tile_rows=None`` the whole array is one tile (flat layout).
    """
    n, p = ids.shape
    nd = -(-dim // d_blk)
    tile_rows = n if tile_rows is None else int(tile_rows)
    ids = _pad_rows(ids, tile_rows)
    vals = _pad_rows(vals, tile_rows)
    nt = ids.shape[0] // tile_rows
    gpt = -(-tile_rows // b_blk)
    ids_t = _pad_rows(ids.reshape(nt, tile_rows, p).swapaxes(0, 1),
                      gpt * b_blk).swapaxes(0, 1) \
        if tile_rows % b_blk else ids.reshape(nt, tile_rows, p)
    vals_t = _pad_rows(vals.reshape(nt, tile_rows, p).swapaxes(0, 1),
                       gpt * b_blk).swapaxes(0, 1) \
        if tile_rows % b_blk else vals.reshape(nt, tile_rows, p)
    t = nt * gpt
    blk = (ids_t // d_blk).reshape(t, b_blk * p).astype(jnp.int32)
    live = (vals_t != 0.0).reshape(t, b_blk * p).astype(jnp.int32)
    occ = jnp.zeros((t, nd), jnp.int32)
    return occ.at[jnp.arange(t)[:, None], blk].max(live)


def pick_n_head(n_rows: int, dim: int, *, d_blk: int = DEFAULT_D_BLK,
                head_bytes: int = DEFAULT_HEAD_BYTES,
                with_counts: bool = True) -> int:
    """How many trailing (high-df) D-blocks the byte budget can cache."""
    nd = -(-dim // d_blk)
    per_block = n_rows * d_blk * 4 * (2 if with_counts else 1)
    if per_block <= 0:
        return 0
    return max(0, min(nd, head_bytes // per_block))


def head_slabs(ids, vals, *, dim: int, d_blk: int = DEFAULT_D_BLK,
               n_head: int = 0,
               with_counts: bool = True):
    """Densify the trailing ``n_head`` D-blocks once: (head, headc).

    Built with the kernels' own ``_densify`` walk so the cached slab is
    operation-for-operation what the kernel would have recomputed.
    """
    from repro.kernels.sparse_sim import densify_rows

    densify = jax.jit(densify_rows, static_argnums=3,
                      static_argnames="counts")
    if n_head <= 0:
        return None, None
    rem = (-ids.shape[1]) % 8            # the wrappers' P alignment
    if rem:
        ids = jnp.pad(ids, ((0, 0), (0, rem)))
        vals = jnp.pad(vals, ((0, 0), (0, rem)))
    d_pad = (-(-dim // d_blk)) * d_blk
    parts_v, parts_c = [], []
    for h in range(n_head):
        d0 = d_pad - (n_head - h) * d_blk
        if with_counts:
            slab, cslab = densify(ids, vals, d0, d_blk, counts=True)
            parts_c.append(cslab)
        else:
            slab = densify(ids, vals, d0, d_blk)
        parts_v.append(slab)
    head = jnp.concatenate(parts_v, axis=1)
    return head, (jnp.concatenate(parts_c, axis=1) if with_counts else None)


def prepare_plan(ids, vals, *, dim: int, b_blk: int | None = None,
                 d_blk: int | None = None,
                 tile_rows: int | None = None,
                 head_bytes: int | None = None,
                 with_counts: bool = True,
                 tuned=None) -> KernelPlan:
    """Build the full plan for a corpus (chunk): tiled occupancy + cached
    head slabs.  Rows are padded to the tile multiple so the plan arrays
    reshape per tile exactly like the data arrays they ride beside.

    ``tuned`` (a :class:`repro.tune.config.TunedConfig`) supplies the block
    geometry and head budget when the explicit kwargs are omitted, and is
    carried on the returned plan so every kernel consuming it launches with
    the same tuned parameters the plan was laid out for."""
    if b_blk is None:
        b_blk = tuned.b_blk if tuned is not None else DEFAULT_B_BLK
    if d_blk is None:
        d_blk = tuned.d_blk if tuned is not None else DEFAULT_D_BLK
    if head_bytes is None:
        head_bytes = (tuned.head_bytes if tuned is not None
                      else DEFAULT_HEAD_BYTES)
    ids = jnp.asarray(ids)
    vals = jnp.asarray(vals)
    if tile_rows:
        ids = _pad_rows(ids, tile_rows)
        vals = _pad_rows(vals, tile_rows)
    occ = occupancy_map(ids, vals, dim=dim, b_blk=b_blk, d_blk=d_blk,
                        tile_rows=tile_rows)
    n_head = pick_n_head(ids.shape[0], dim, d_blk=d_blk,
                         head_bytes=head_bytes, with_counts=with_counts)
    head, headc = head_slabs(ids, vals, dim=dim, d_blk=d_blk, n_head=n_head,
                             with_counts=with_counts)
    return KernelPlan(occ=occ, head=head, headc=headc, b_blk=b_blk,
                      d_blk=d_blk, n_head=0 if head is None else n_head,
                      dim=dim, tuned=tuned)

"""Backend-pluggable clustering primitives (DESIGN.md §5).

The six algorithms in :mod:`repro.core.assignment` are pure selection logic
over a small set of accumulators (exact similarities, region-wise partial
sums, filter survivor masks), and the update phase (Alg. 6) is two segment
reductions (cluster sums, ρ_self refresh).  This module owns *how* both
phases' accumulators are produced:

``reference``
    Assignment: the TAAT slot loop over padded object tuples, run to each
    tile's live width.  Update: the dense ``at[].add`` scatter and the
    own-centroid gather.  Runs everywhere, no alignment constraints, and is
    the exactness oracle every other backend is tested against.

``pallas``
    Assignment: the TPU Pallas kernels in :mod:`repro.kernels.ops`
    (``sparse_sim`` / ``esicp_gather`` / ``esicp_filter``).  Update:
    ``segment_update`` (scatter-add as one-hot-selection MXU matmuls) and
    ``rho_gather`` (ρ_self refresh as a one-hot own-centroid gather).
    Off-TPU the kernels run in interpret mode (handled inside
    ``kernels.ops``), so the backend is selectable — and tested — on CPU.
    The TA bound needs a *per-object* value threshold, which the
    shared-threshold gather kernel cannot express; that one mode delegates
    to the reference scan (see the AFM translation table in DESIGN.md §3).
    ``prepare`` builds the epoch-invariant :class:`repro.kernels.plan.
    KernelPlan` (occupancy map + cached high-df head slabs) that every
    kernel of a fit reuses — documents never change across Lloyd
    iterations, so their densified form is computed once per chunk per fit.

Exactness contract: for every algorithm, both backends produce identical
assignments and moving flags from identical state.  ``mult`` diagnostics are
kept exactly equal too — the kernels carry the visited (object-term,
posting-entry) pair count as a fused accumulator off the same one-hot walk
that builds the value slab, so ``diag=True`` costs no extra kernel launch.
Means and ρ_self agree to float32 reduction-order tolerance (the MXU
accumulates in a different order than the sequential scatter).

``xla_blocked``
    The same skew-aware plan expressed as pure jit-compiled XLA programs
    (:mod:`repro.kernels.xla_blocked`): Zipf tail as gather + posting-sum
    (work ∝ postings — the limiting case of occupancy skipping), optional
    high-df head region as one cached dense slab GEMM per call, and all
    four algo-mode accumulators fused into a single pass each — including
    TA (per-object threshold, natively compiled here) and CS (one
    ``cs_gather`` where Pallas needs three launches).  This is the engine
    that actually *compiles* off-TPU, so it is what ``auto`` picks on
    CPU/GPU and what the CI compiled ratchet enforces.

Selection: pass ``backend="reference" | "pallas" | "xla_blocked" | "auto"``
anywhere a ``backend=`` argument is threaded (``SphericalKMeans``,
``assignment_step``, ``update_step``, ``distributed.kmeans``,
``serve.ClusterEngine``, ``benchmarks.common``).  ``auto`` resolves to
``pallas`` on TPU and ``xla_blocked`` elsewhere.
"""
from __future__ import annotations

from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.sparse import SparseDocs
from repro.core.meanindex import MeanIndex, doc_sketch


def col_ok_mask(index: MeanIndex, xstate: jax.Array) -> jax.Array:
    """(B, K) — centroids the ICP filter allows: moving ones always; invariant
    ones only for objects that are not 'more similar' (Eq. 5)."""
    return index.moving[None, :] | ~xstate[:, None]


@runtime_checkable
class Backend(Protocol):
    """Producer of the assignment-step and update-step accumulators.

    Assignment phase — ``accumulate`` returns the same dict the reference
    TAAT scan produces:

      mode 'exact'  -> {sims, mult}
      mode 'esicp'  -> {sims, rho12, y, mult}
      mode 'ta'     -> {sims, rho12, y, mult}   (per-object v_ta threshold)
      mode 'cs'     -> {sims, rho1, sq, mult}

    ``with_counts=True`` (diag required) additionally returns ``counts`` —
    the RAW per-(object, centroid) visited-pair counts of the mode's exact
    region, *without* the ICP ``col_ok`` mask (``mult`` keeps applying it).
    The bounds/sketch algo modes re-weight these per-row for their honest
    Mult accounting.

    ``es_filter`` evaluates the ES upper bound (Eq. 4) and returns the
    survivor mask and per-object candidate counts |Z_i|.

    ``sketch_sim`` produces the (B, K) block-vector sketch similarity used
    by the sketch gate: each entry upper-bounds the exact cosine similarity
    (per-group Cauchy-Schwarz on non-negative data).  The doc sketches come
    from the shared :func:`repro.core.meanindex.doc_sketch`, so both
    backends gate on bitwise-identical sketches.

    Update phase (Alg. 6) — both methods take raw padded tuple arrays so the
    single-device driver and the shard-local distributed step share them;
    callers pre-mask dead slots / invalid rows to ``vals == 0``:

    ``accumulate_means`` — (K, dim) tentative cluster sums λ_j = Σ_{x∈C_j} x
    (lines 2–5).  Out-of-range assignments contribute nothing.  ``init``
    lets chunked callers fold partial sums in place.

    ``self_sims`` — (B,) refreshed ρ_{a(i)} vs each object's own (new)
    centroid (lines 6–7); out-of-range assignments read ρ = 0.

    Prepared plans — ``prepare`` builds whatever per-corpus(-chunk) cache
    the backend can exploit across the iterations of one fit; every other
    method accepts it back as ``plan=``.  Documents are constant across
    Lloyd iterations, so anything derived from the tuples alone (dense
    slabs, occupancy) is epoch-invariant.  ``None`` (the reference
    backend's answer) means "nothing to cache"; callers pass it straight
    through, and a plan built for a different row layout is ignored by the
    consumer — plans are an optimisation, never a correctness input.

    Tuned configs — ``prepare`` additionally consults the process-wide
    :data:`repro.tune.TUNED_CACHE` when ``tune != "off"``: ``"cached"``
    reuses a previously found winner for this corpus regime (falling back
    to defaults on a miss), ``"search"`` runs the roofline-pruned autotuner
    on a miss under the opt-in ``tune_budget`` and caches the winner.  The
    winning :class:`repro.tune.TunedConfig` rides the returned plan, so
    every kernel of the fit launches with the tuned geometry.  ``k`` (the
    cluster count the fit will use) keys the signature; without it there is
    nothing to tune against and the knob is a no-op.
    """

    name: str

    def prepare(self, docs: SparseDocs, *, tile_rows: int | None = None,
                with_counts: bool = True, k: int | None = None,
                tune: str = "off", tune_budget=None): ...

    def accumulate(self, docs: SparseDocs, index: MeanIndex, xstate: jax.Array,
                   *, mode: str, v_ta: jax.Array | None = None,
                   diag: bool = True, unroll: bool | int = False,
                   p_block: int | None = None, plan=None,
                   with_counts: bool = False) -> dict: ...

    def es_filter(self, rho12: jax.Array, y: jax.Array, rho_self: jax.Array,
                  col_ok: jax.Array, v_th: jax.Array): ...

    def sketch_sim(self, docs: SparseDocs, index: MeanIndex, *,
                   plan=None) -> jax.Array: ...

    def accumulate_means(self, ids: jax.Array, vals: jax.Array,
                         assign: jax.Array, *, k: int, dim: int,
                         init: jax.Array | None = None,
                         plan=None) -> jax.Array: ...

    def self_sims(self, ids: jax.Array, vals: jax.Array, assign: jax.Array,
                  means_t: jax.Array, *, plan=None) -> jax.Array: ...


# ---------------------------------------------------------------------------
# Reference backend: the TAAT slot loop.
# ---------------------------------------------------------------------------

def _pad_p(ids, vals, pb: int):
    """Pad the tuple-width axis to a ``pb`` multiple with dead (id 0, val 0)
    slots — dead slots are ``live == False`` everywhere downstream."""
    p = ids.shape[1]
    rem = (-p) % pb
    if rem:
        ids = jnp.pad(ids, ((0, 0), (0, rem)))
        vals = jnp.pad(vals, ((0, 0), (0, rem)))
    return ids, vals


SCAN_FOLD = 3


def scan_fold(p: int) -> int:
    """Slots :func:`reference_scan` folds into each rewrite of its (B, K)
    carries when the caller names none: ``SCAN_FOLD``, but never more than
    the tile's ``p`` slots.  Up to three slots XLA keeps the carries'
    updates and the Mult count in one fusion on a TPU v5e; at four the
    count, and from eight each carry, becomes a fusion of its own that reads
    every gathered block again (DESIGN.md, "The fold")."""
    return max(min(SCAN_FOLD, p), 1)


def reference_scan(docs: SparseDocs, index: MeanIndex, xstate, *, mode: str,
                   v_ta: jax.Array | None = None, diag: bool = True,
                   unroll: bool | int = False, p_block: int | None = None,
                   with_counts: bool = False):
    """One fused TAAT pass — the paper's MIVI loop order (Alg. 1 lines 1–5).

    On TPU each slot is one (B,)-gather of a posting row ξ_s block plus a
    rank-1 multiply-add on the (B, K) accumulators: no data-dependent
    branches, shared thresholds as masks.

    ``sims`` is always the full exact similarity (reference semantics); the
    CPU algorithm would only compute it for survivors — that cost is what the
    verify-mult term in the caller accounts for.

    The fold: each loop step gathers ``p_block`` consecutive slots and adds
    them to the carries one slot after another, so a document's slots are
    added 0…nnz−1, one slot per add, whatever the fold; only the number of
    times the (B, K) carries are written back changes (once per fold, not
    once per slot).  ``p_block=None`` takes :func:`scan_fold` of the tile's
    P.  A masked carry multiplies the slot's value by the masked row, so
    every carry takes one multiply and one add per slot.  The fold keeps
    the order of operations, so results do not depend on it wherever the
    compiler does not contract a multiply and an add into one rounding (on
    the CPU the float carries were seen to differ by at most 1 ulp;
    ``mult`` and ``counts`` are exact).

    Other knobs (the distributed step and the dry-run coster thread them
    through):
      diag=False  — skip the Mult count (``mult`` is returned as 0);
      unroll      — a static scan over all P slots, unrolled (dry-run
                    exact-FLOPs costing).

    Without ``unroll`` the loop runs only to the tile's live width,
    ``max(nnz)`` rounded up to a whole fold: every slot past it is dead in
    every row, and a dead slot adds exact zeros to every carry.  The result
    is bit-identical to the scan over all P slots.
    """
    b, p = docs.ids.shape
    k = index.k
    t_th = index.params.t_th
    v_th = index.params.v_th
    means_t = index.means_t
    col_ok = col_ok_mask(index, xstate)      # (B, K) — ICP lane mask
    f32 = jnp.float32
    pb = scan_fold(p) if p_block is None else max(int(p_block), 1)
    assert not with_counts or diag, "with_counts requires diag=True"

    def slot(carry, idp, vp):
        """One slot's adds: ids and values (B,)."""
        rows = means_t[idp]                   # (B, K) posting rows
        contrib = vp[:, None] * rows
        out = dict(carry, sims=carry["sims"] + contrib)
        live = (vp != 0.0)[:, None]           # (B, 1)
        if diag:
            nz = (rows > 0) & col_ok & live
            # Raw visited pairs (no ICP mask) — the per-(B, K) twin the
            # Pallas diag accumulator produces; ``mult`` keeps col_ok.
            nzr = (rows > 0) & live
        if mode == "exact":
            if diag:
                out["mult"] = carry["mult"] + jnp.sum(nz, dtype=f32)
                if with_counts:
                    out["counts"] = carry["counts"] + nzr.astype(f32)
        elif mode in ("esicp", "ta"):
            tail = (idp >= t_th)[:, None]     # (B, 1)
            # TA: a per-object threshold (Eq. 16).
            hi = rows >= (v_th if mode == "esicp" else v_ta[:, None])
            exact_mask = jnp.where(tail, hi, True)
            out["rho12"] = carry["rho12"] + vp[:, None] * jnp.where(
                exact_mask, rows, 0.0)
            out["y"] = carry["y"] + jnp.where(tail & ~hi, vp[:, None], 0.0)
            # TA walks each sorted posting until v < v_ta: visits hi entries
            # plus one terminator comparison; mults are the hi entries.
            if diag:
                out["mult"] = carry["mult"] + jnp.sum(nz & exact_mask,
                                                      dtype=f32)
                if with_counts:
                    out["counts"] = carry["counts"] + (
                        nzr & exact_mask).astype(f32)
        elif mode == "cs":
            tail = (idp >= t_th)[:, None]
            out["rho1"] = carry["rho1"] + vp[:, None] * jnp.where(
                tail, 0.0, rows)
            out["sq"] = carry["sq"] + rows * jnp.where(tail & live, rows, 0.0)
            if diag:
                out["mult"] = carry["mult"] + jnp.sum(nz, dtype=f32)
        else:
            raise ValueError(mode)
        return out

    def body(carry, xs):
        idp, vp = xs                          # (pb, B), (pb, B)
        for j in range(pb):                   # slot order, one add each
            carry = slot(carry, idp[j], vp[j])
        return carry, None

    carry = {"sims": jnp.zeros((b, k), f32), "mult": jnp.zeros((), f32)}
    if with_counts:
        assert mode in ("exact", "esicp"), mode
        carry["counts"] = jnp.zeros((b, k), f32)
    if mode == "esicp" or mode == "ta":
        carry["rho12"] = jnp.zeros((b, k), f32)
        carry["y"] = jnp.zeros((b, k), f32)
    elif mode == "cs":
        carry["rho1"] = jnp.zeros((b, k), f32)
        carry["sq"] = jnp.zeros((b, k), f32)
    ids, vals = _pad_p(docs.ids, docs.vals, pb)
    pp = ids.shape[1]
    xs = (ids.T.reshape(pp // pb, pb, b), vals.T.reshape(pp // pb, pb, b))
    if unroll:
        out, _ = jax.lax.scan(body, carry, xs, unroll=unroll)
        return out
    return jax.lax.fori_loop(
        0, live_steps(docs.nnz, pp, pb),
        lambda s, c: body(c, (xs[0][s], xs[1][s]))[0], carry)


def live_steps(nnz: jax.Array, p: int, pb: int = 1) -> jax.Array:
    """Slot steps of :func:`reference_scan` over a tile: its longest row's
    ``nnz`` (at most ``p``) in blocks of ``pb`` slots, rounded up."""
    w = jnp.minimum(jnp.max(nnz, initial=0), p)
    return (w + pb - 1) // pb


def gather_verify_scan(ids, vals, nnz, means_t, t_th, v_th, rho_max, col_ok,
                       *, unroll: bool | int = False, p_block: int = 1,
                       p_tail: int = 16):
    """Paper-faithful two-phase ES assignment (§Perf variant, Algs. 2–3) —
    the reference backend's gather/verify scan, shared with the distributed
    shard-local step.

    Phase G: one TAAT pass accumulating only (rho12, y) — the full exact
    similarity is NOT computed for every centroid (that is MIVI's cost).
    Phase V: the exact Region-3 partial from a second pass over a compacted
    live-suffix window.  ids ascend by df-rank within a row, so the >= t_th
    entries are the last (ntH)_i LIVE positions; the caller guarantees
    max_i (ntH)_i <= p_tail (computed after EstParams fixes t_th — the same
    moment the paper restructures its index).  Exactness is preserved:
    windows that reach below position 0 are validity-masked.

    Returns (exact_masked, survivors).
    """
    c, p = ids.shape
    k_loc = means_t.shape[1]
    pb = max(int(p_block), 1)
    z = jnp.zeros((c, k_loc), jnp.float32)

    def g_body(carry, xs):
        rho12, y = carry
        idp, vp = xs
        rows = means_t[idp]
        contrib = vp[..., None] * rows
        tail = (idp >= t_th)[..., None]
        hi = rows >= v_th
        exact = jnp.where(tail, hi, True)
        return (rho12 + jnp.sum(jnp.where(exact, contrib, 0.0), 0),
                y + jnp.sum(jnp.where(tail & ~hi, vp[..., None], 0.0), 0)), None

    gi, gv = _pad_p(ids, vals, pb)
    pp = gi.shape[1]
    xs = (gi.T.reshape(pp // pb, pb, c), gv.T.reshape(pp // pb, pb, c))
    (rho12, y), _ = jax.lax.scan(g_body, (z, z), xs, unroll=unroll)
    surv = ((rho12 + y * v_th) > rho_max[:, None]) & col_ok

    # compacted live-suffix window [nnz - p_tail, nnz)
    off = nnz[:, None] - p_tail + jnp.arange(p_tail)[None, :]
    okw = off >= 0
    idx = jnp.clip(off, 0, p - 1)
    tids = jnp.take_along_axis(ids, idx, axis=1)
    tvals = jnp.where(okw, jnp.take_along_axis(vals, idx, axis=1), 0.0)

    def v_body(rho3, xs):
        idp, vp = xs
        rows = means_t[idp]
        tail = (idp >= t_th)[..., None]
        lo = rows < v_th
        add = jnp.where(tail & lo, vp[..., None] * rows, 0.0)
        return rho3 + jnp.sum(add, 0), None

    ti, tv = _pad_p(tids, tvals, pb)
    pt = ti.shape[1]
    xsv = (ti.T.reshape(pt // pb, pb, c), tv.T.reshape(pt // pb, pb, c))
    rho3, _ = jax.lax.scan(v_body, z, xsv, unroll=unroll)
    exact = jnp.where(surv, rho12 + rho3, -jnp.inf)
    return exact, surv


class ReferenceBackend:
    """Pure-jnp TAAT scan — runs anywhere, defines the exactness contract."""

    name = "reference"

    def prepare(self, docs, *, tile_rows=None, with_counts=True, k=None,
                tune="off", tune_budget=None):
        # The scan gathers posting rows directly from the sparse tuples —
        # there is no densified intermediate to cache, and no launch
        # geometry to tune.
        return None

    def accumulate(self, docs, index, xstate, *, mode, v_ta=None, diag=True,
                   unroll=False, p_block=None, plan=None, with_counts=False):
        return reference_scan(docs, index, xstate, mode=mode, v_ta=v_ta,
                              diag=diag, unroll=unroll, p_block=p_block,
                              with_counts=with_counts)

    def es_filter(self, rho12, y, rho_self, col_ok, v_th):
        # Upper bound (Eq. 4): rho12 + y·v_th.  The paper's App.-A scaling
        # removes this multiply on CPU; on TPU it is a fused multiply-add.
        ub = rho12 + y * v_th
        survivors = (ub > rho_self[:, None]) & col_ok
        return survivors, jnp.sum(survivors, axis=1).astype(jnp.int32)

    def sketch_sim(self, docs, index, *, plan=None):
        sk = doc_sketch(docs.ids, docs.vals, index.dim)
        # HIGHEST: the sketch product is an upper bound pruning trusts;
        # TPU's default f32 dot may round its operands to bf16.
        return jnp.dot(sk, index.sketch_t, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)

    def accumulate_means(self, ids, vals, assign, *, k, dim, init=None,
                         plan=None):
        # The dense scatter-add (Alg. 6 lines 2–5).  XLA drops out-of-bounds
        # scatter updates, so out-of-range assignments contribute nothing.
        acc = jnp.zeros((k, dim), jnp.float32) if init is None else init
        return acc.at[assign[:, None], ids].add(vals)

    def self_sims(self, ids, vals, assign, means_t, *, plan=None):
        # Own-centroid gather (Alg. 6 lines 6–7); gathers clamp out-of-range
        # assignments, so they are masked to ρ = 0 explicitly.
        k = means_t.shape[1]
        picked = means_t[ids, jnp.minimum(assign, k - 1)[:, None]]
        return jnp.sum(jnp.where((assign < k)[:, None], vals * picked, 0.0),
                       axis=1)


# ---------------------------------------------------------------------------
# Pallas backend: kernels for the hot accumulators.
# ---------------------------------------------------------------------------

class PallasBackend:
    """Kernel-dispatching backend (interpret mode off-TPU).

    The similarity/gather accumulators become densify-then-MXU kernels.  The
    Mult diagnostic — a *count* of posting entries a CPU implementation
    would visit — rides the SAME launches as a fused accumulator

        count[b, k] = Σ_p live[b, p] · W[ids[b, p], k]

    (W the region/nonzero indicator of the mean matrix, built in-kernel from
    the means block): the one-hot walk that densifies the value slab yields
    the live-count slab for free, so ``diag=True`` issues no extra kernel
    launch and no host-side (D, K) region mask exists anymore.  The ES mode
    also pulls the full exact similarity out of the same gather launch.

    ``prepare`` densifies the high-df head region once per chunk per fit and
    precomputes the (B-tile, D-block) occupancy map (kernels/plan.py) —
    the caches every kernel of the fit then reuses via ``plan=``.
    """

    name = "pallas"

    def prepare(self, docs, *, tile_rows=None, with_counts=True, k=None,
                tune="off", tune_budget=None):
        from repro.kernels.plan import prepare_plan

        tuned = None
        if tune != "off":
            from repro.tune import ensure_tuned

            tuned = ensure_tuned(docs, k=k, mode=tune, budget=tune_budget)
        # The cache is built from row_mask()-masked vals — the operand
        # convention of the update phase.  The assignment phase feeds the
        # kernels raw docs.vals; the two coincide under the repo-wide
        # invariant that slots at index >= nnz hold val 0 (corpus builders,
        # pad_rows and the DocStoreBuilder all enforce it), which is the
        # precondition for one cached slab serving both phases.
        vals = jnp.where(docs.row_mask(), docs.vals, 0.0)
        return prepare_plan(docs.ids, vals, dim=docs.dim,
                            tile_rows=tile_rows, with_counts=with_counts,
                            tuned=tuned)

    def accumulate(self, docs, index, xstate, *, mode, v_ta=None, diag=True,
                   unroll=False, p_block=None, plan=None, with_counts=False):
        # unroll / p_block are reference-scan tiling knobs; the kernels tile
        # via their own block specs, so both are accepted and ignored here.
        from repro.kernels import ops

        assert not with_counts or diag, "with_counts requires diag=True"
        if mode == "ta":
            # Per-object v_ta threshold: not expressible as a shared-threshold
            # mask over the (D_blk, K_sup) means block, so no kernel exists.
            return reference_scan(docs, index, xstate, mode="ta", v_ta=v_ta)

        means_t = index.means_t
        t_th = index.params.t_th
        v_th = index.params.v_th
        col_ok = col_ok_mask(index, xstate)

        out = {}
        if not diag:
            out["mult"] = jnp.zeros((), jnp.float32)
        if mode == "exact" or mode == "cs":
            res = ops.sparse_sim(docs.ids, docs.vals, means_t, diag=diag,
                                 plan=plan)
            if diag:
                out["sims"], counts = res
                out["mult"] = jnp.sum(jnp.where(col_ok, counts, 0.0))
                if with_counts:
                    # The fused diag accumulator is already the raw
                    # per-(B, K) count — same launch, no extra kernel.
                    out["counts"] = counts
            else:
                out["sims"] = res
            if mode == "cs":
                # These substitute synthetic weights for the raw vals, so the
                # cached head slabs do not apply (occupancy is re-derived
                # from the actual operands inside the wrapper); the tuned
                # launch geometry still does.
                tuned = plan.tuned if plan is not None else None
                # Head-only partial: mask on the object side (ids < t_th) —
                # identical sums to masking rows of the mean matrix.
                head_vals = jnp.where(docs.ids < t_th, docs.vals, 0.0)
                out["rho1"] = ops.sparse_sim(docs.ids, head_vals, means_t,
                                             tuned=tuned)
                # Σ over live tail slots of means², as the reference scan.
                tail_ones = ((docs.ids >= t_th)
                             & (docs.vals != 0.0)).astype(jnp.float32)
                out["sq"] = ops.sparse_sim(docs.ids, tail_ones,
                                           means_t * means_t, tuned=tuned)
        elif mode == "esicp":
            # ONE launch for the whole gathering phase: bound operands, the
            # exact similarities, and (under diag) the exact-region visited-
            # pair counts, all off one densified slab per (B, D) block.
            res = ops.esicp_gather(docs.ids, docs.vals, means_t, t_th, v_th,
                                   with_sims=True, diag=diag, plan=plan)
            if diag:
                out["rho12"], out["y"], out["sims"], counts = res
                out["mult"] = jnp.sum(jnp.where(col_ok, counts, 0.0))
                if with_counts:
                    out["counts"] = counts
            else:
                out["rho12"], out["y"], out["sims"] = res
        else:
            raise ValueError(mode)
        return out

    def es_filter(self, rho12, y, rho_self, col_ok, v_th):
        from repro.kernels import ops

        mask, count = ops.esicp_filter(rho12, y, rho_self, col_ok, v_th)
        return mask.astype(bool), count

    def sketch_sim(self, docs, index, *, plan=None):
        from repro.kernels import ops

        sk = doc_sketch(docs.ids, docs.vals, index.dim)
        return ops.sketch_sim(sk, index.sketch_t, plan=plan)

    def accumulate_means(self, ids, vals, assign, *, k, dim, init=None,
                         plan=None):
        # Scatter-add as one-hot-selection MXU matmuls: a TPU must not
        # read-modify-write HBM per object (kernels/segment_update.py).
        from repro.kernels import ops

        lam = ops.segment_update(assign, ids, vals, k=k, d=dim, plan=plan)
        return lam if init is None else init + lam

    def self_sims(self, ids, vals, assign, means_t, *, plan=None):
        from repro.kernels import ops

        return ops.rho_gather(assign, ids, vals, means_t, plan=plan)


# ---------------------------------------------------------------------------
# XLA-blocked backend: the compiled skew-aware engine for non-TPU hardware.
# ---------------------------------------------------------------------------

class XlaBlockedBackend:
    """Pure-XLA kernel twins (:mod:`repro.kernels.xla_blocked`).

    Same plan vocabulary as the Pallas backend — ``prepare`` returns a
    :class:`repro.kernels.plan.KernelPlan` and every accumulator accepts it
    back — but the engine consumes only the head-slab cache (the gather
    formulation makes ``occ`` redundant: empty cells are never touched).
    The engine *default* is head-less (``head_bytes=0``): on CPU the slab
    GEMM costs B·H·K FLOPs against the gather's B·p_head·K, so caching head
    blocks is an autotuner decision (``tune != "off"`` with an
    ``engine="xla_blocked"`` winner), not a reflex.

    Every algo mode is a single fused launch here: exact/esicp via the
    shared-threshold ops, TA natively (the per-object threshold rides the
    gather, no reference-scan delegation), CS via the one-pass
    ``cs_gather`` (sims + rho1 + sq + counts together).
    """

    name = "xla_blocked"

    def prepare(self, docs, *, tile_rows=None, with_counts=True, k=None,
                tune="off", tune_budget=None):
        from repro.kernels.plan import prepare_plan

        tuned = None
        if tune != "off":
            from repro.tune import ensure_tuned

            tuned = ensure_tuned(docs, k=k, mode=tune, budget=tune_budget,
                                 engine=self.name)
        # Same masked-vals convention as the Pallas prepare (one cached slab
        # serves both phases); head_bytes=0 unless a tuned config says
        # otherwise, see the class docstring.
        vals = jnp.where(docs.row_mask(), docs.vals, 0.0)
        head_bytes = tuned.head_bytes if tuned is not None else 0
        return prepare_plan(docs.ids, vals, dim=docs.dim,
                            tile_rows=tile_rows, with_counts=with_counts,
                            head_bytes=head_bytes, tuned=tuned)

    def accumulate(self, docs, index, xstate, *, mode, v_ta=None, diag=True,
                   unroll=False, p_block=None, plan=None, with_counts=False):
        # unroll / p_block are reference-scan tiling knobs; the XLA ops
        # chunk the posting axis themselves, so both are accepted + ignored.
        from repro.kernels import xla_blocked as xb

        assert not with_counts or diag, "with_counts requires diag=True"
        means_t = index.means_t
        t_th = index.params.t_th
        v_th = index.params.v_th
        col_ok = col_ok_mask(index, xstate)

        out = {}
        if not diag:
            out["mult"] = jnp.zeros((), jnp.float32)
        if mode == "exact":
            res = xb.sparse_sim(docs.ids, docs.vals, means_t, diag=diag,
                                plan=plan)
            if diag:
                out["sims"], counts = res
                out["mult"] = jnp.sum(jnp.where(col_ok, counts, 0.0))
                if with_counts:
                    out["counts"] = counts
            else:
                out["sims"] = res
        elif mode == "cs":
            res = xb.cs_gather(docs.ids, docs.vals, means_t, t_th, diag=diag)
            if diag:
                out["sims"], out["rho1"], out["sq"], counts = res
                out["mult"] = jnp.sum(jnp.where(col_ok, counts, 0.0))
            else:
                out["sims"], out["rho1"], out["sq"] = res
        elif mode in ("esicp", "ta"):
            res = xb.esicp_gather(docs.ids, docs.vals, means_t, t_th, v_th,
                                  v_ta=v_ta if mode == "ta" else None,
                                  with_sims=True, diag=diag, plan=plan)
            if diag:
                out["rho12"], out["y"], out["sims"], counts = res
                out["mult"] = jnp.sum(jnp.where(col_ok, counts, 0.0))
                if with_counts:
                    out["counts"] = counts
            else:
                out["rho12"], out["y"], out["sims"] = res
        else:
            raise ValueError(mode)
        return out

    # The filter and sketch phases are already single fused XLA expressions
    # in the reference backend — reuse them verbatim.
    es_filter = ReferenceBackend.es_filter
    sketch_sim = ReferenceBackend.sketch_sim

    def accumulate_means(self, ids, vals, assign, *, k, dim, init=None,
                         plan=None):
        from repro.kernels import xla_blocked as xb

        lam = xb.segment_update(assign, ids, vals, k=k, d=dim, plan=plan)
        return lam if init is None else init + lam

    def self_sims(self, ids, vals, assign, means_t, *, plan=None):
        from repro.kernels import xla_blocked as xb

        return xb.rho_gather(assign, ids, vals, means_t, plan=plan)


# ---------------------------------------------------------------------------
# Registry / resolution.
# ---------------------------------------------------------------------------

BACKENDS: dict[str, Backend] = {
    "reference": ReferenceBackend(),
    "pallas": PallasBackend(),
    "xla_blocked": XlaBlockedBackend(),
}


def resolve_backend(spec) -> Backend:
    """'reference' | 'pallas' | 'xla_blocked' | 'auto' | Backend -> Backend.

    'auto' picks the engine that actually compiles on the local hardware:
    the Pallas kernels on TPU, the XLA-blocked twins everywhere else
    (interpret-mode Pallas is for correctness testing, not speed, and the
    reference scan is the oracle, not the fast path).
    """
    if isinstance(spec, Backend) and not isinstance(spec, str):
        return spec
    if spec == "auto":
        return BACKENDS["pallas" if jax.default_backend() == "tpu"
                        else "xla_blocked"]
    if spec not in BACKENDS:
        raise ValueError(
            f"unknown backend {spec!r}; one of {sorted(BACKENDS)} or 'auto'")
    return BACKENDS[spec]

"""Compiled skew-aware XLA twins of the clustering kernels (DESIGN.md §3/§5).

The Pallas kernels in this package only *compile* on TPU; everywhere else
they run in interpret mode, which validates semantics but loses every race
(BENCH_kernels.json showed 0.1–0.9× the reference scan on CPU).  This module
re-expresses the same skew-aware execution plan — high-df head slab reused
across the epoch, cheap Zipf tail, fused diagnostics — as pure jit-compiled
XLA programs, so the engine wins on the hardware CI actually has:

* **Zipf tail → gather + posting-sum.**  Each op gathers only the mean rows
  its live postings name (``means_t[ids]`` → (B, P-chunk, K)) and folds them
  with one einsum per chunk.  Work is proportional to *postings*, not to the
  dense (B, D) grid — this is the limiting case of the occupancy map: an
  empty (B-tile, D-block) cell is simply never touched, exactly, so the ops
  do not consume ``plan.occ`` at all (SIVF's skip list degenerates to "only
  walk the postings you have" once there is no dense grid to mask).

* **High-df head → one densified slab matmul.**  When a :class:`repro.
  kernels.plan.KernelPlan` carries cached head slabs, postings in the
  trailing (high-df) D-blocks leave the gather and ride a single dense
  ``head @ means_head`` GEMM per call — the dense-head/sparse-tail split of
  Knittel, Koch & Ertl (arxiv 2108.00895), amortised across the fused-epoch
  scan because the slab is densified once per chunk per fit.  The count twin
  ``headc`` feeds the fused Mult diagnostic the same way.  Note the engine
  *default* is head-less (``XLA_HEAD_BYTES = 0``): on CPU the slab GEMM
  costs ``B·H·K`` FLOPs against the gather's ``B·p_head·K``, so it only
  pays off when the autotuner's measured search says so.

* **Fused diagnostics.**  ``diag=True`` returns the raw visited-pair counts
  off the same gather/GEMM pass — identical semantics to the Pallas fused
  accumulator and the reference scan (live postings × nonzero mean entries,
  exact-region-masked for esicp/ta).

* **Update phase.**  ``segment_update`` is the native scatter-add
  (out-of-range assignments dropped), ``rho_gather`` the own-centroid
  gather — both already proportional to nnz, no plan needed.

Exactness contract: identical to the other backends — integer accumulators
(Mult, counts, y for unit vals) are bit-exact; float sums agree to
reduction-order tolerance; assignments are bit-identical in the parity
matrix.  The head split changes the *addition order* of the similarity sums
(slab GEMM + tail gather vs one posting walk), which is why the head is an
explicit opt-in rather than silently on.

Signature compatibility: the wrappers accept and ignore the Pallas launch
geometry kwargs (``b_blk`` / ``k_blk`` / ``d_blk`` / ``k_sup`` / ``tuned``
/ ``interpret``) so call sites, tests and the autotuner can drive either
engine with one argument vocabulary — XLA has no grid to shape; the only
plan-derived knob that matters here is the head split.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

f32 = jnp.float32
# Full-f32 contraction: TPU's default f32 dot may round operands to bf16.
_HIGHEST = jax.lax.Precision.HIGHEST

# Byte budget for the gathered (B, P-chunk, K) mean-row block per fold step.
# Shapes are static, so the chunk count is resolved at trace time; one chunk
# (a single einsum, no scan) is the common case at bench/fit shapes.
ROWS_BUDGET = 32 << 20


def _chunks(ids, vals, k: int):
    """Split the posting axis into gather-budget chunks.

    Returns ``(nc, xs)`` where each of ``xs = (ids, vals)`` is shaped
    (nc, B, pc).  Chunk padding is dead (id 0, val 0), like the caller's
    own padding."""
    b, p = ids.shape
    pc = int(max(1, min(p, ROWS_BUDGET // max(1, b * k * 4))))
    rem = (-p) % pc
    if rem:
        ids = jnp.pad(ids, ((0, 0), (0, rem)))
        vals = jnp.pad(vals, ((0, 0), (0, rem)))
    nc = (p + rem) // pc
    resh = lambda a: a.reshape(b, nc, pc).transpose(1, 0, 2)
    return nc, (resh(ids), resh(vals))


def _gather_fold(ids, vals, means_t, fold, init):
    """Fold ``fold(acc, idp, vp, rows)`` over P-chunks of the postings,
    gathering ``rows = means_t[idp]`` per chunk.  Single-chunk calls skip
    the scan entirely (one gather + one fold in straight-line HLO)."""
    nc, (cids, cvals) = _chunks(ids, vals, means_t.shape[1])
    if nc == 1:
        return fold(init, cids[0], cvals[0], means_t[cids[0]])

    def body(acc, xs):
        idp, vp = xs
        return fold(acc, idp, vp, means_t[idp]), None

    acc, _ = jax.lax.scan(body, init, (cids, cvals))
    return acc


def _head_split(plan, b: int, d: int, means_t, *, need_counts: bool):
    """Resolve the plan's head cache against this call's geometry.

    Returns ``(d0, head, headc, means_h)`` — ``d0`` the first head term id
    in the plan's padded D space, ``means_h`` the zero-padded head rows of
    the mean matrix — or all-``None`` when the plan is absent or was built
    for a different layout (plans are an optimisation, never a correctness
    input: a mismatched plan is ignored, not an error)."""
    none = (None, None, None, None)
    if plan is None or plan.head is None or plan.n_head <= 0:
        return none
    if plan.dim != d or plan.head.shape[0] != b:
        return none
    if plan.head.shape[1] != plan.n_head * plan.d_blk:
        return none
    if need_counts and plan.headc is None:
        return none
    d_pad = (-(-d // plan.d_blk)) * plan.d_blk
    d0 = d_pad - plan.n_head * plan.d_blk
    means_h = jnp.pad(means_t, ((0, d_pad - d), (0, 0)))[d0:]
    return d0, plan.head, plan.headc if need_counts else None, means_h


def _mask_head(ids, vals, d0):
    """Zero out postings the head slab already covers (ids >= d0) so they
    leave the gather; liveness-derived counts vanish with the value."""
    return vals if d0 is None else jnp.where(ids < d0, vals, 0.0)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=f32, precision=_HIGHEST)


@partial(jax.jit, static_argnames=("diag", "tuned", "b_blk", "k_blk",
                                  "d_blk", "k_sup", "interpret"))
def sparse_sim(ids, vals, means_t, *, plan=None, tuned=None, diag=False,
               b_blk=None, k_blk=None, d_blk=None, k_sup=None,
               interpret=None):
    """(B, K) exact similarities x·μ; ``diag=True`` adds the raw visited-pair
    counts (live postings × nonzero mean entries) off the same pass."""
    b = ids.shape[0]
    d, k = means_t.shape
    d0, head, headc, means_h = _head_split(plan, b, d, means_t,
                                           need_counts=diag)
    tvals = _mask_head(ids, vals, d0)

    def fold(acc, idp, vp, rows):
        sims = acc[0] + jnp.einsum("bp,bpk->bk", vp, rows,
                                   preferred_element_type=f32,
                                   precision=_HIGHEST)
        if not diag:
            return (sims,)
        live = (vp != 0.0).astype(f32)
        cnt = acc[1] + jnp.einsum("bp,bpk->bk", live,
                                  (rows > 0).astype(f32),
                                  preferred_element_type=f32,
                                  precision=_HIGHEST)
        return (sims, cnt)

    init = (jnp.zeros((b, k), f32),) * (2 if diag else 1)
    out = _gather_fold(ids, tvals, means_t, fold, init)
    sims = out[0]
    if head is not None:
        sims = sims + _dot(head, means_h)
    if not diag:
        return sims
    counts = out[1]
    if head is not None:
        counts = counts + _dot(headc, (means_h > 0).astype(f32))
    return sims, counts


@partial(jax.jit, static_argnames=("with_sims", "diag", "tuned", "b_blk",
                                   "k_blk", "d_blk", "k_sup", "interpret"))
def esicp_gather(ids, vals, means_t, t_th, v_th, *, v_ta=None, plan=None,
                 tuned=None, with_sims=False, diag=False, b_blk=None,
                 k_blk=None, d_blk=None, k_sup=None, interpret=None):
    """ES/ICP gathering phase: (rho12, y[, sims][, counts]) in ONE pass.

    ``v_ta`` switches the exact-region test from the shared ``v_th`` to the
    per-object TA threshold (Eq. 16) — natively compiled here, where the
    Pallas backend must delegate TA to the reference scan (a per-object
    threshold cannot mask a shared (D_blk, K_sup) means block).  The head
    slab only applies to the shared-threshold form: its region masks depend
    on (term, mean) alone, so they commute with the per-term value sums the
    slab caches; a per-object threshold does not.
    """
    b = ids.shape[0]
    d, k = means_t.shape
    per_object = v_ta is not None
    if per_object:
        d0 = head = headc = means_h = None
    else:
        d0, head, headc, means_h = _head_split(plan, b, d, means_t,
                                               need_counts=diag)
    tvals = _mask_head(ids, vals, d0)
    thr = v_ta[:, None, None] if per_object else v_th

    def fold(acc, idp, vp, rows):
        tail = (idp >= t_th)[..., None]
        hi = rows >= thr
        exact = jnp.where(tail, hi, True)
        contrib = vp[..., None] * rows
        out = {"rho12": acc["rho12"]
               + jnp.sum(jnp.where(exact, contrib, 0.0), 1),
               "y": acc["y"]
               + jnp.sum(jnp.where(tail & ~hi, vp[..., None], 0.0), 1)}
        if with_sims:
            out["sims"] = acc["sims"] + jnp.sum(contrib, 1)
        if diag:
            live = (vp != 0.0)[..., None]
            out["counts"] = acc["counts"] + jnp.sum(
                (rows > 0) & live & exact, 1, dtype=f32)
        return out

    init = {"rho12": jnp.zeros((b, k), f32), "y": jnp.zeros((b, k), f32)}
    if with_sims:
        init["sims"] = jnp.zeros((b, k), f32)
    if diag:
        init["counts"] = jnp.zeros((b, k), f32)
    out = _gather_fold(ids, tvals, means_t, fold, init)
    if head is not None:
        # Term-indexed region masks: every posting of head term t shares
        # tail/hi status, so the per-term value sums in ``head`` (and live
        # counts in ``headc``) distribute over them exactly.
        term = jnp.arange(d0, d0 + means_h.shape[0])[:, None]
        tail_h = term >= t_th
        hi_h = means_h >= v_th
        exact_h = jnp.where(tail_h, hi_h, True)
        out["rho12"] = out["rho12"] + _dot(head,
                                           jnp.where(exact_h, means_h, 0.0))
        out["y"] = out["y"] + _dot(head, (tail_h & ~hi_h).astype(f32))
        if with_sims:
            out["sims"] = out["sims"] + _dot(head, means_h)
        if diag:
            out["counts"] = out["counts"] + _dot(
                headc, ((means_h > 0) & exact_h).astype(f32))
    res = (out["rho12"], out["y"])
    if with_sims:
        res += (out["sims"],)
    if diag:
        res += (out["counts"],)
    return res


@partial(jax.jit, static_argnames=("diag", "tuned", "interpret"))
def cs_gather(ids, vals, means_t, t_th, *, plan=None, tuned=None, diag=False,
              interpret=None):
    """CS partials (sims, rho1, sq[, counts]) in ONE fused pass — the Pallas
    backend needs three ``sparse_sim`` launches for the same accumulators.

    No head split: ``sq`` sums means² over the live slots with
    ``id >= t_th``, which the live-count slab cannot express; precedent is
    the Pallas backend bypassing its head cache for CS too."""
    b = ids.shape[0]
    k = means_t.shape[1]

    def fold(acc, idp, vp, rows):
        tail = (idp >= t_th)[..., None]
        live = (vp != 0.0)[..., None]
        contrib = vp[..., None] * rows
        out = {"sims": acc["sims"] + jnp.sum(contrib, 1),
               "rho1": acc["rho1"] + jnp.sum(jnp.where(tail, 0.0, contrib), 1),
               "sq": acc["sq"] + jnp.sum(jnp.where(tail & live, rows * rows,
                                                   0.0), 1)}
        if diag:
            out["counts"] = acc["counts"] + jnp.sum(
                (rows > 0) & live, 1, dtype=f32)
        return out

    init = {kk: jnp.zeros((b, k), f32) for kk in
            (("sims", "rho1", "sq", "counts") if diag
             else ("sims", "rho1", "sq"))}
    out = _gather_fold(ids, vals, means_t, fold, init)
    res = (out["sims"], out["rho1"], out["sq"])
    return res + (out["counts"],) if diag else res


@partial(jax.jit, static_argnames=("k", "d", "tuned", "b_blk", "k_blk",
                                   "d_blk", "k_sup", "interpret"))
def segment_update(assign, ids, vals, *, k: int, d: int, plan=None,
                   tuned=None, b_blk=None, k_blk=None, d_blk=None,
                   k_sup=None, interpret=None):
    """(K, D) cluster sums λ_j = Σ_{x∈C_j} x as a native scatter-add —
    already proportional to nnz, so there is nothing for a plan to cache.
    Out-of-range assignments are dropped (Alg. 6 lines 2–5)."""
    rows = jnp.broadcast_to(assign[:, None], ids.shape)
    return jnp.zeros((k, d), f32).at[rows, ids].add(vals, mode="drop")


@partial(jax.jit, static_argnames=("tuned", "b_blk", "k_blk", "d_blk",
                                   "k_sup", "interpret"))
def rho_gather(assign, ids, vals, means_t, *, plan=None, tuned=None,
               b_blk=None, k_blk=None, d_blk=None, k_sup=None,
               interpret=None):
    """(B,) ρ_self refresh: own-centroid gather over each row's postings;
    out-of-range assignments read ρ = 0 (Alg. 6 lines 6–7)."""
    k = means_t.shape[1]
    picked = means_t[ids, jnp.minimum(assign, k - 1)[:, None]]
    return jnp.sum(jnp.where((assign < k)[:, None], vals * picked, 0.0),
                   axis=1)

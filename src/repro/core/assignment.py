"""Assignment step for all compared algorithms (paper Algs. 1–5, App. F).

The algorithms here are pure selection logic: they consume accumulators
(exact similarities, region-wise partial sums, survivor masks) produced by a
pluggable :class:`repro.core.backends.Backend` — ``reference`` (the TAAT
slot loop, the paper's MIVI loop order and this repo's exactness oracle)
or ``pallas`` (the TPU kernels in :mod:`repro.kernels.ops`, interpret mode
off-TPU).  See backends.py / DESIGN.md §5 for the split.

Exactness contract (tested): every algorithm returns *identical* assignments
to MIVI from the same state, under every backend.  Filters only change the
Mult/CPR diagnostics, which are counted as the paper counts them — the
number of multiply-adds a CPU implementation would execute, i.e. pairs
(object-term, posting-entry) actually visited.

Tie policy (paper Algs. 1/2 line "if ρ_j > ρ_max"): strict improvement over
the refreshed self-similarity; among equal improvers the lowest centroid ID
wins (sequential scan order == jnp.argmax first-occurrence).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.sparse import SparseDocs
from repro.core.meanindex import (MeanIndex, doc_sketch, sketch_group_width,
                                  sketch_size)
from repro.core.backends import col_ok_mask, reference_scan, resolve_backend
from repro.core.update import n_ub_groups, ub_group_of, ub_group_size

# Back-compat alias: property/kernel tests exercise the oracle scan directly.
_scan = reference_scan
_col_ok = col_ok_mask


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class AssignResult:
    assign: jax.Array        # (B,) int32 — new a(i)
    rho: jax.Array           # (B,) float32 — similarity to the winner
    n_candidates: jax.Array  # (B,) int32 — |Z_i| (CPR numerator)
    mult: jax.Array          # () float32 — multiply-adds the CPU algo executes
    changed: jax.Array       # (B,) bool — assignment changed
    ub: jax.Array            # (B, G) float32 — refreshed per-bound-group
    #                          upper bounds on the best non-assigned
    #                          similarity (bounds modes; other algorithms
    #                          pass the caller's value through).  G =
    #                          n_ub_groups(k), see core/update.py.

    def tree_flatten(self):
        return (self.assign, self.rho, self.n_candidates, self.mult,
                self.changed, self.ub), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(*leaves)


def _finalize(sims_masked, prev_assign, rho_self):
    """Sequential 'if ρ_j > ρ_max' semantics, vectorised."""
    best_j = jnp.argmax(sims_masked, axis=1).astype(jnp.int32)
    best = jnp.take_along_axis(sims_masked, best_j[:, None], axis=1)[:, 0]
    improve = best > rho_self
    assign = jnp.where(improve, best_j, prev_assign)
    rho = jnp.where(improve, best, rho_self)
    return assign, rho


def _nt_tail(docs: SparseDocs, t_th) -> jax.Array:
    """(B,) — (ntH)_i: live tuples with term id >= t_th."""
    return jnp.sum((docs.ids >= t_th) & docs.row_mask(), axis=1).astype(jnp.int32)


def default_ub(rho_self: jax.Array, k: int) -> jax.Array:
    """(B, G) 'no bound known' upper bounds: +inf (never prune, never loosen).

    Dead/padding rows follow the ρ_self = 0 convention in the *state* (see
    core/update.py init), but as an algorithm input +inf is always sound.
    """
    return jnp.full((rho_self.shape[0], n_ub_groups(k)), jnp.inf, jnp.float32)


def _second_best(sims: jax.Array, assign: jax.Array) -> jax.Array:
    """(B,) — max_{j != assign_i} sims[i, j]: the tight bound refresh."""
    cols = jnp.arange(sims.shape[1], dtype=jnp.int32)[None, :]
    masked = jnp.where(cols == assign[:, None], -jnp.inf, sims)
    return jnp.max(masked, axis=1)


def _group_bounds(b: jax.Array, assign: jax.Array, k: int) -> jax.Array:
    """(B, G) — per-bound-group max of the per-centroid bound matrix ``b``
    (B, K), with each object's ASSIGNED centroid excluded (the group bound
    is on the best *non-assigned* similarity).  The ragged final group pads
    with -inf, so phantom centroids never inflate a bound; a singleton
    group holding only the assigned centroid refreshes to -inf — soundly
    'nothing to find here' (non-finite, so drift never loosens it)."""
    cols = jnp.arange(k, dtype=jnp.int32)[None, :]
    masked = jnp.where(cols == assign[:, None], -jnp.inf, b)
    gsz = ub_group_size(k)
    g = n_ub_groups(k)
    masked = jnp.pad(masked, ((0, 0), (0, g * gsz - k)),
                     constant_values=-jnp.inf)
    return jnp.max(masked.reshape(masked.shape[0], g, gsz), axis=2)


def _sketch_pairs(docs: SparseDocs, index: MeanIndex) -> jax.Array:
    """(B, K) f32 — sketch-product multiplications per (object, centroid).

    The paper's Mult convention counts pairs actually visited; a sparse
    implementation of the sketch product Σ_g ||x_g||·||c_g|| multiplies only
    groups where BOTH sketches are nonzero — a short document touches at
    most nnz_i groups, so the sketch check costs ≤ min(nnz_i, S) per
    centroid, never the dense S.  Backend-independent by construction
    (shared ``doc_sketch`` + the index's ``sketch_t``), so Mult parity
    across backends is preserved bit-for-bit.
    """
    dsk = doc_sketch(docs.ids, docs.vals, index.dim) > 0.0
    csk = index.sketch_t > 0.0
    return jnp.dot(dsk.astype(jnp.float32), csk.astype(jnp.float32),
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


# The compound mode refines the ES bound with a Region-3 sketch check only
# when the crude bound sits within striking distance of the threshold:
# rho12 + BETA·y·v_th <= ρ_self.  Fat-margin survivors are real candidates
# that no bound refinement can prune (measured: ~0 prune rate), so paying
# the sketch check on them is a guaranteed net loss; thin-margin survivors
# are exactly where the per-group Cauchy–Schwarz bound can beat y·v_th.
SKETCH_MARGIN_BETA = 0.5


def _region3_bound(docs: SparseDocs, index: MeanIndex):
    """Sketch-refined Region-3 bound: ((B, K) bound, (B, K) check cost).

    The block-vector sketch applied *within* the index's region structure
    (sketch × index regions): per-group L2 norms of the document tail
    (ids >= t_th) against per-group norms of each centroid's Region-3
    entries (id >= t_th and v < v_th).  Per-group Cauchy–Schwarz bounds the
    exact Region-3 partial — usually far tighter than the paper's y·v_th,
    which prices every Region-3 entry at the threshold.  The cost twin
    counts group pairs where both sketches are live (the sparse-product
    convention of :func:`_sketch_pairs`).  Shared jnp code on both backends,
    so Mult parity is bitwise.
    """
    d = index.dim
    g = sketch_group_width(d)
    s = sketch_size(d)
    t_th = index.params.t_th
    v_th = index.params.v_th
    seg = jnp.clip(docs.ids.astype(jnp.int32) // g, 0, s - 1)
    tv = jnp.where((docs.ids >= t_th) & docs.row_mask(), docs.vals, 0.0)
    dsk = jnp.sqrt(jax.vmap(
        lambda sg, v: jax.ops.segment_sum(v * v, sg, num_segments=s))(seg, tv))
    rows = jnp.arange(d, dtype=jnp.int32)
    r3 = jnp.where((rows[:, None] >= t_th) & (index.means_t < v_th),
                   index.means_t, 0.0)
    csk = jnp.sqrt(jax.ops.segment_sum(r3 * r3, rows // g, num_segments=s))
    # HIGHEST: a bound pruning trusts must not lose bits to bf16 passes.
    bound = jnp.dot(dsk, csk, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
    pairs = jnp.dot((dsk > 0.0).astype(jnp.float32),
                    (csk > 0.0).astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
    return bound, pairs


# ---------------------------------------------------------------------------
# Algorithms.  Each takes the backend as its first argument.
# ---------------------------------------------------------------------------

def _mivi(bk, docs, index, prev_assign, rho_self, xstate, plan=None, ub=None):
    """Alg. 1 — exact TAAT over the mean-inverted index, no filters."""
    ub = default_ub(rho_self, index.k) if ub is None else ub
    no_icp = jnp.zeros_like(xstate)
    out = bk.accumulate(docs, index, no_icp, mode="exact", plan=plan)
    assign, rho = _finalize(out["sims"], prev_assign, rho_self)
    k = index.k
    return AssignResult(assign, rho,
                        n_candidates=jnp.full(assign.shape, k, jnp.int32),
                        mult=out["mult"], changed=assign != prev_assign,
                        ub=ub)


def _icp(bk, docs, index, prev_assign, rho_self, xstate, plan=None, ub=None):
    """Auxiliary filter only (Kaukoranta+): skip invariant centroids for
    'more similar' objects."""
    ub = default_ub(rho_self, index.k) if ub is None else ub
    out = bk.accumulate(docs, index, xstate, mode="exact", plan=plan)
    col_ok = col_ok_mask(index, xstate)
    sims = jnp.where(col_ok, out["sims"], -jnp.inf)
    assign, rho = _finalize(sims, prev_assign, rho_self)
    n_cand = jnp.sum(col_ok, axis=1).astype(jnp.int32)
    return AssignResult(assign, rho, n_cand, out["mult"],
                        assign != prev_assign, ub)


def _es_core(bk, docs, index, prev_assign, rho_self, xstate, plan=None,
             ub=None):
    """ES upper bound + optional ICP: Algs. 2/3 (and 4/5 with scaling)."""
    ub = default_ub(rho_self, index.k) if ub is None else ub
    out = bk.accumulate(docs, index, xstate, mode="esicp", plan=plan)
    v_th = index.params.v_th
    col_ok = col_ok_mask(index, xstate)
    survivors, n_cand = bk.es_filter(out["rho12"], out["y"], rho_self,
                                     col_ok, v_th)
    sims = jnp.where(survivors, out["sims"], -jnp.inf)
    assign, rho = _finalize(sims, prev_assign, rho_self)
    # Verification phase cost: |Z_i| exact Region-3 partials, (ntH)_i mults each.
    verify_mult = jnp.sum(n_cand.astype(jnp.float32) * _nt_tail(docs, index.params.t_th))
    return AssignResult(assign, rho, n_cand, out["mult"] + verify_mult,
                        assign != prev_assign, ub)


def _esicp(bk, docs, index, prev_assign, rho_self, xstate, plan=None, ub=None):
    return _es_core(bk, docs, index, prev_assign, rho_self, xstate, plan, ub)


def _es(bk, docs, index, prev_assign, rho_self, xstate, plan=None, ub=None):
    """Ablation: ES main filter without ICP (App. D)."""
    return _es_core(bk, docs, index, prev_assign, rho_self,
                    jnp.zeros_like(xstate), plan, ub)


def _ta_icp(bk, docs, index, prev_assign, rho_self, xstate, plan=None,
            ub=None):
    """TA-ICP (App. F-A): per-object threshold v_ta = ρ_max / ||x||_1."""
    ub_in = default_ub(rho_self, index.k) if ub is None else ub
    l1 = jnp.sum(docs.vals, axis=1)                       # ||x_i||_1 (vals >= 0)
    # ρ_max = -inf encodes "no history" (iteration 1): clamp to 0 so the
    # threshold degenerates to v_ta = 0 (everything exact, nothing pruned)
    # instead of poisoning the bound with 0·(-inf) = NaN.
    v_ta = jnp.maximum(rho_self, 0.0) / jnp.maximum(l1, 1e-12)
    out = bk.accumulate(docs, index, xstate, mode="ta", v_ta=v_ta,
                        plan=plan)
    col_ok = col_ok_mask(index, xstate)
    ub = out["rho12"] + out["y"] * v_ta[:, None]
    # G_(ta) line 10: centroids with zero partial similarity are skipped —
    # their bound v_ta·y <= v_ta·||x||_1 = ρ_max can never strictly win.
    survivors = (out["rho12"] > 0.0) & (ub > rho_self[:, None]) & col_ok
    sims = jnp.where(survivors, out["sims"], -jnp.inf)
    assign, rho = _finalize(sims, prev_assign, rho_self)
    n_cand = jnp.sum(survivors, axis=1).astype(jnp.int32)
    verify_mult = jnp.sum(n_cand.astype(jnp.float32) * _nt_tail(docs, index.params.t_th))
    return AssignResult(assign, rho, n_cand, out["mult"] + verify_mult,
                        assign != prev_assign, ub_in)


def _cs_icp(bk, docs, index, prev_assign, rho_self, xstate, plan=None,
            ub=None):
    """CS-ICP (App. F-B): Cauchy–Schwarz bound on the tail subspace."""
    ub_in = default_ub(rho_self, index.k) if ub is None else ub
    tail_mask = (docs.ids >= index.params.t_th) & docs.row_mask()
    x_tail_l2 = jnp.sqrt(jnp.sum(jnp.where(tail_mask, docs.vals, 0.0) ** 2, axis=1))
    out = bk.accumulate(docs, index, xstate, mode="cs", plan=plan)
    col_ok = col_ok_mask(index, xstate)
    ub = out["rho1"] + x_tail_l2[:, None] * jnp.sqrt(out["sq"])
    survivors = (ub > rho_self[:, None]) & col_ok
    sims = jnp.where(survivors, out["sims"], -jnp.inf)
    assign, rho = _finalize(sims, prev_assign, rho_self)
    n_cand = jnp.sum(survivors, axis=1).astype(jnp.int32)
    verify_mult = jnp.sum(n_cand.astype(jnp.float32) * _nt_tail(docs, index.params.t_th))
    return AssignResult(assign, rho, n_cand, out["mult"] + verify_mult,
                        assign != prev_assign, ub_in)


# ---------------------------------------------------------------------------
# Bound-maintenance / sketch-gated modes (ISSUE 7; DESIGN.md §11).
#
# All three compute the FULL exact similarity matrix and finalize over it
# unmasked — assignments are bit-identical to `mivi` per backend by
# construction, unconditionally.  The bounds/sketch machinery drives only
# the honest Mult / |Z_i| accounting (what a CPU implementation exploiting
# the same pruning would pay) and the maintained `ub` state.
# ---------------------------------------------------------------------------

def _bounds(bk, docs, index, prev_assign, rho_self, xstate, plan=None,
            ub=None):
    """Cosine-adapted Elkan/Hamerly bound maintenance (arxiv_2107.04074),
    per centroid GROUP (Yinyang-style: core/update.py's UB_GROUPS tiers).

    A bound group whose drift-loosened upper bound is <= the object's
    refreshed ρ_self cannot hold a strict improver, so the CPU algorithm
    skips every posting entry of that group's centroids; an object with NO
    active group skips the scan outright.  Active groups pay their exact
    gather cost and refresh to the true per-group max non-assigned
    similarity; skipped groups carry the loosened bound forward
    (update_step loosens each group by its own centroids' worst drift).
    """
    k = index.k
    ub = default_ub(rho_self, k) if ub is None else ub
    no_icp = jnp.zeros_like(xstate)
    out = bk.accumulate(docs, index, no_icp, mode="exact", plan=plan,
                        with_counts=True)
    assign, rho = _finalize(out["sims"], prev_assign, rho_self)
    ga = ub > rho_self[:, None]                           # (B, G) group active
    pa = jnp.take(ga, ub_group_of(k), axis=1)             # (B, K) per-centroid
    mult = jnp.sum(jnp.where(pa, out["counts"], 0.0))
    n_cand = jnp.sum(pa, axis=1).astype(jnp.int32)
    ub_new = jnp.where(ga, _group_bounds(out["sims"], assign, k), ub)
    return AssignResult(assign, rho, n_cand, mult, assign != prev_assign,
                        ub_new)


def _sketch(bk, docs, index, prev_assign, rho_self, xstate, plan=None,
            ub=None):
    """Block-vector sketch pre-filter (arxiv_2108.00895).

    A (B, S) x (S, K) sketch similarity — an upper bound on the exact
    cosine for non-negative data — gates the exact pass: only centroids
    whose sketch bound beats ρ_self are scanned exactly.  The sketch check
    itself is charged sparsely (:func:`_sketch_pairs`): a document's sketch
    has at most nnz_i live groups, so the pre-filter costs a fraction of
    the exact row scan it screens.  Rows with ρ_self <= 0 cannot prune
    (every bound beats the threshold), so the CPU algorithm skips the
    sketch pass for them and pays the plain MIVI cost — iteration-1 Mult
    is exactly MIVI's.
    """
    ub = default_ub(rho_self, index.k) if ub is None else ub
    no_icp = jnp.zeros_like(xstate)
    out = bk.accumulate(docs, index, no_icp, mode="exact", plan=plan,
                        with_counts=True)
    sk_sims = bk.sketch_sim(docs, index, plan=plan)
    assign, rho = _finalize(out["sims"], prev_assign, rho_self)
    k = index.k
    rho_pos = rho_self > 0.0
    surv = sk_sims > rho_self[:, None]
    gathered = jnp.sum(jnp.where(surv, out["counts"], 0.0), axis=1)
    full = jnp.sum(out["counts"], axis=1)
    sk_cost = jnp.sum(_sketch_pairs(docs, index), axis=1)
    mult = jnp.sum(jnp.where(rho_pos, sk_cost + gathered, full))
    n_cand = jnp.where(rho_pos, jnp.sum(surv, axis=1), k).astype(jnp.int32)
    return AssignResult(assign, rho, n_cand, mult, assign != prev_assign, ub)


def _bounds_esicp(bk, docs, index, prev_assign, rho_self, xstate, plan=None,
                  ub=None):
    """Compounded pruning: bounds x index regions (ES + ICP) x sketch.

    Gate order a CPU implementation would run, cheapest first:
      1. bounds  — drift-loosened ub <= ρ_self: skip the object outright;
      2. ICP     — invariant centroids for 'more similar' objects (free:
                   reuses last iteration's membership deltas);
      3. ES      — Region-1/2 partial + Region-3 L1 bound (the paper's
                   main filter, at its EstParams operating point);
      4. sketch  — margin-gated Region-3 sketch refinement: thin-margin
                   ES survivors get the tighter per-group Cauchy–Schwarz
                   bound before their verify window is paid;
      5. verify  — exact Region-3 partial for the |Z_i| final survivors.
    The sketch layer composes *inside* the region structure rather than in
    front of it: a full-vector sketch check costs about as much as the ES
    Region-1/2 scan it would gate (measured), so the only placement with
    positive expected value is refining the crude y·v_th tail bound — and
    only where the crude margin is thin (SKETCH_MARGIN_BETA).

    The refreshed ub is assembled honestly from what each gate actually
    knows per centroid (exact sim / refined bound / ES bound / ρ_self for
    ICP-skipped columns) — never from similarities a pruned scan would not
    have computed.
    """
    k = index.k
    ub = default_ub(rho_self, k) if ub is None else ub
    out = bk.accumulate(docs, index, xstate, mode="esicp", plan=plan,
                        with_counts=True)
    v_th = index.params.v_th
    col_ok = col_ok_mask(index, xstate)
    ga = ub > rho_self[:, None]                           # (B, G) group active
    pa = jnp.take(ga, ub_group_of(k), axis=1)             # (B, K) per-centroid
    gate = col_ok & pa
    crude, _ = bk.es_filter(out["rho12"], out["y"], rho_self, gate, v_th)
    r3_bound, r3_pairs = _region3_bound(docs, index)
    es_ub = out["rho12"] + out["y"] * v_th
    ref_ub = out["rho12"] + jnp.minimum(out["y"] * v_th, r3_bound)
    checked = crude & (out["rho12"] + SKETCH_MARGIN_BETA * out["y"] * v_th
                       <= rho_self[:, None])
    survivors = crude & jnp.where(checked, ref_ub > rho_self[:, None], True)
    n_cand = jnp.sum(survivors, axis=1).astype(jnp.int32)
    assign, rho = _finalize(out["sims"], prev_assign, rho_self)
    gather_mult = jnp.sum(jnp.where(gate, out["counts"], 0.0))
    sketch_mult = jnp.sum(jnp.where(checked, r3_pairs, 0.0))
    verify_mult = jnp.sum(n_cand.astype(jnp.float32)
                          * _nt_tail(docs, index.params.t_th))
    # Honest per-centroid bound from whichever gate pruned it (centroids in
    # inactive groups keep +inf here; their group's old bound is retained
    # by the jnp.where(ga, ...) below, so the +inf never escapes).
    b = jnp.where(survivors, out["sims"], jnp.inf)
    b = jnp.minimum(b, jnp.where(checked, ref_ub, jnp.inf))
    b = jnp.minimum(b, jnp.where(gate, es_ub, jnp.inf))
    b = jnp.minimum(b, jnp.where(pa & ~col_ok, rho_self[:, None], jnp.inf))
    ub_new = jnp.where(ga, _group_bounds(b, assign, k), ub)
    return AssignResult(assign, rho, n_cand,
                        gather_mult + sketch_mult + verify_mult,
                        assign != prev_assign, ub_new)


ALGORITHMS = {
    "mivi": _mivi,
    "icp": _icp,
    "es": _es,
    "esicp": _esicp,
    "ta-icp": _ta_icp,
    "cs-icp": _cs_icp,
    "bounds": _bounds,
    "sketch": _sketch,
    "bounds-esicp": _bounds_esicp,
}


def assign_batch(algo: str, backend, docs: SparseDocs, index: MeanIndex,
                 prev_assign: jax.Array, rho_self: jax.Array,
                 xstate: jax.Array, plan=None, ub=None) -> AssignResult:
    """Un-jitted dispatch — the traceable core shared by ``assignment_step``
    and the fused epoch in :mod:`repro.core.lloyd`.

    ``plan`` is the backend's prepared epoch-invariant cache
    (``Backend.prepare``) for exactly these ``docs``; None is always valid.
    ``ub`` is the maintained (B, G) per-object, per-bound-group upper bound
    (bounds modes); None means 'no bound known' (+inf — never prunes).
    """
    if algo not in ALGORITHMS:
        raise ValueError(f"unknown algorithm {algo!r}; one of {sorted(ALGORITHMS)}")
    bk = resolve_backend(backend)
    return ALGORITHMS[algo](bk, docs, index, prev_assign, rho_self, xstate,
                            plan, ub)


@partial(jax.jit, static_argnames=("algo", "backend"))
def assignment_step(algo: str, docs: SparseDocs, index: MeanIndex,
                    prev_assign: jax.Array, rho_self: jax.Array,
                    xstate: jax.Array, backend: str = "reference",
                    plan=None, ub=None) -> AssignResult:
    """One assignment step over a batch of objects.

    prev_assign: (B,) int32 — a(i) from the previous iteration.
    rho_self:    (B,) float32 — ρ_{a(i)}^{[r-1]}, refreshed at the last update
                 step (Alg. 6 lines 6–7), the shared pruning threshold ρ_max.
    xstate:      (B,) bool — Eq. (5) 'more similar' flag for the ICP filter.
    backend:     'reference' | 'pallas' | 'auto' (see core/backends.py).
    plan:        optional prepared kernel plan for these docs
                 (``Backend.prepare``; see kernels/plan.py).
    ub:          optional (B, G) maintained per-group upper bound (bounds
                 modes; G = n_ub_groups(k), core/update.py).
    """
    return assign_batch(algo, backend, docs, index, prev_assign, rho_self,
                        xstate, plan, ub)

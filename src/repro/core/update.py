"""Update step (paper Alg. 6) + clustering state.

Responsibilities, matching the paper's five update-phase duties:
  (1) accumulate tentative means λ_j = Σ_{x∈C_j} x (sparse segment sum);
  (2) refresh every object's self-similarity ρ_{a(i)} against its *new*
      centroid — the shared pruning threshold of the next assignment step;
  (3)–(5) rebuild the structured index (here: column stats + moving flags).

Both segment reductions — (1) and (2) — are produced by the pluggable
:class:`repro.core.backends.Backend` (``reference``: dense scatter / gather,
the exactness oracle; ``pallas``: the ``segment_update`` / ``rho_gather``
MXU kernels).  Invariant-centroid detection uses exact set semantics
(C_j^{[r]} == C_j^{[r-1]}) — a centroid is invariant iff no object moved
into or out of its cluster — rather than a float tolerance, so ICP pruning
is exactly the paper's under every backend.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.sparse import SparseDocs
from repro.core.meanindex import (MeanIndex, StructuralParams,
                                  build_mean_index, normalized_means)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class KMeansState:
    index: MeanIndex
    assign: jax.Array       # (N,) int32
    rho_self: jax.Array     # (N,) float32 — ρ_{a(i)} vs the current means
    rho_self_prev: jax.Array  # (N,) float32 — previous refresh (Eq. 5 input)
    iteration: jax.Array    # () int32
    ub: jax.Array           # (N, G) float32 — drift-loosened upper bounds on
    #                         the best non-assigned similarity per centroid
    #                         BOUND GROUP (bounds modes; +inf = no bound
    #                         known, the init value).  G = n_ub_groups(k):
    #                         per-center when k <= UB_GROUPS, else centroids
    #                         tier into ceil(k/G)-wide groups so one fast-
    #                         moving outlier center only voids its own
    #                         group's bound (Yinyang-style group filter,
    #                         cosine-adapted).

    def tree_flatten(self):
        return (self.index, self.assign, self.rho_self, self.rho_self_prev,
                self.iteration, self.ub), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(*leaves)

    @property
    def xstate(self) -> jax.Array:
        """Eq. (5): object is 'more similar' if its refreshed self-similarity
        did not decrease.  False on the first two iterations (no history)."""
        return (self.rho_self >= self.rho_self_prev) & (self.iteration >= 2)


# Additive slack on the drift-loosened bound: absorbs the float32 rounding
# of the arccos/cos round trip so the loosened bound stays a TRUE upper
# bound on the drifted similarity (hypothesis-tested in test_pruning.py).
UB_DRIFT_EPS = 1e-5

# Bound-group cap: per-object bounds are maintained per centroid GROUP, one
# bound per center up to this many, then ceil(k/UB_GROUPS)-wide tiers.  The
# scalar (Hamerly-style) bound dies the moment ANY center moves fast — and
# early Lloyd iterations always have a few outlier movers (measured: median
# drift 5–10°, max 55–70° at iteration 2).  Grouping confines an outlier's
# drift to its own group, so the other groups' bounds keep pruning.
UB_GROUPS = 16


def ub_group_size(k: int) -> int:
    """Centroids per bound group: 1 while k <= UB_GROUPS (true per-center
    bounds), else the smallest tier width that fits UB_GROUPS groups."""
    return -(-k // min(k, UB_GROUPS))


def n_ub_groups(k: int) -> int:
    """G — number of bound groups (= state width of ``KMeansState.ub``)."""
    return -(-k // ub_group_size(k))


def ub_group_of(k: int) -> jax.Array:
    """(K,) int32 — static centroid-id → bound-group map (contiguous tiers,
    matching the 'model'-axis column sharding so a mesh shard's centroids
    land in contiguous groups)."""
    return jnp.arange(k, dtype=jnp.int32) // ub_group_size(k)


def max_center_drift(means_t_new: jax.Array,
                     means_t_old: jax.Array) -> jax.Array:
    """() float32 — max_j angular drift arccos(<c_j_new, c_j_old>).

    Both operands are unit columns ((D, K) transposed means); empty clusters
    keep their previous mean (normalized_means), so their drift is exactly
    zero and never loosens anyone's bound.
    """
    dots = jnp.sum(means_t_new * means_t_old, axis=0)
    return jnp.max(jnp.arccos(jnp.clip(dots, -1.0, 1.0)))


def group_drift(means_t_new: jax.Array,
                means_t_old: jax.Array) -> jax.Array:
    """(G,) float32 — per-bound-group max angular drift (the per-center
    drift aggregated over each group's centroids).  Pads with zero drift,
    so a ragged final group is never loosened by phantom centroids."""
    dots = jnp.sum(means_t_new * means_t_old, axis=0)
    d = jnp.arccos(jnp.clip(dots, -1.0, 1.0))
    k = d.shape[0]
    gsz = ub_group_size(k)
    g = n_ub_groups(k)
    d = jnp.pad(d, (0, g * gsz - k))
    return jnp.max(d.reshape(g, gsz), axis=1)


def drift_loosen(ub: jax.Array, delta_max: jax.Array) -> jax.Array:
    """Loosen per-object similarity upper bounds by the center drift.

    Spherical triangle inequality: if ρ(x, c_old) <= u = cos(θ) then
    ρ(x, c_new) <= cos(max(0, θ − δ)) for any center that rotated by at
    most δ.  Non-finite bounds (+inf 'unknown') pass through unchanged;
    finite ones gain UB_DRIFT_EPS so float rounding never tightens them.

    Elementwise with broadcasting: a (N, G) bound matrix against a (G,)
    per-group drift loosens each group by its own centroids' worst drift.
    """
    theta = jnp.arccos(jnp.clip(ub, -1.0, 1.0))
    loose = jnp.cos(jnp.maximum(theta - delta_max, 0.0)) + UB_DRIFT_EPS
    return jnp.where(jnp.isfinite(ub), loose, ub)


def moving_flags(assign: jax.Array, prev_assign: jax.Array, k: int) -> jax.Array:
    """(K,) bool — exact invariance: a centroid moved iff its membership
    changed (an object entered or left its cluster)."""
    changed = assign != prev_assign
    moving = jnp.zeros((k,), jnp.int32)
    moving = moving.at[assign].max(changed.astype(jnp.int32))
    moving = moving.at[prev_assign].max(changed.astype(jnp.int32))
    return moving.astype(bool)


@partial(jax.jit, static_argnames=("k", "backend"))
def update_step(docs: SparseDocs, assign: jax.Array, prev_assign: jax.Array,
                prev_state: KMeansState, params: StructuralParams, *, k: int,
                backend: str = "reference", plan=None,
                ub: jax.Array | None = None) -> KMeansState:
    """Full update: new means, moving flags, refreshed ρ_self, xstate shift.

    ``plan`` is the backend's prepared epoch-invariant cache for ``docs``
    (``Backend.prepare``; the Lloyd drivers build it once per fit).

    ``ub`` is the assignment step's refreshed per-object bound (bounds
    modes); None keeps the previous state's.  Either way the stored bound
    is loosened by the max per-center angular drift of THIS update, so it
    remains a true upper bound against the new means.

    Its operations carry their phase in their op names: ``update.sums``,
    ``update.normalize``, ``update.index``, ``update.rho`` and
    ``update.bounds``.
    """
    from repro.core.backends import resolve_backend

    bk = resolve_backend(backend)
    with jax.named_scope("update.sums"):
        vals = jnp.where(docs.row_mask(), docs.vals, 0.0)
        lam = bk.accumulate_means(docs.ids, vals, assign, k=k, dim=docs.dim,
                                  plan=plan)
    with jax.named_scope("update.normalize"):
        means = normalized_means(lam, prev_state.index.means_t)
    with jax.named_scope("update.index"):
        index = build_mean_index(means, params,
                                 moving=moving_flags(assign, prev_assign, k))
    with jax.named_scope("update.rho"):
        rho_self = bk.self_sims(docs.ids, vals, assign, index.means_t,
                                plan=plan)
    with jax.named_scope("update.bounds"):
        ub = prev_state.ub if ub is None else ub
        delta = group_drift(index.means_t, prev_state.index.means_t)
        ub = drift_loosen(ub, delta)
    return KMeansState(
        index=index,
        assign=assign,
        rho_self=rho_self,
        rho_self_prev=prev_state.rho_self,
        iteration=prev_state.iteration + 1,
        ub=ub,
    )


def seed_rows(n_docs: int, k: int, *, seed: int = 0) -> jax.Array:
    """(K,) distinct document indices — THE seeding draw.  Shared by the
    resident and the DocStore paths so a one-chunk store fit starts from
    the bitwise-identical centroids as ``fit(docs)``."""
    key = jax.random.PRNGKey(seed)
    return jax.random.choice(key, n_docs, shape=(k,), replace=False)


def seed_centroids(sel: SparseDocs, k: int) -> jax.Array:
    """(K, D) unit-norm means from K seed documents (scatter + L2)."""
    means = jnp.zeros((k, sel.dim), jnp.float32)
    rows = jnp.arange(k)[:, None]
    means = means.at[rows, sel.ids].add(jnp.where(sel.row_mask(), sel.vals, 0.0))
    norms = jnp.sqrt(jnp.sum(means**2, axis=1, keepdims=True))
    return means / jnp.maximum(norms, 1e-12)


def init_state(docs: SparseDocs, k: int, params: StructuralParams, *, seed: int = 0) -> KMeansState:
    """Random seeding: K distinct documents as initial centroids.

    App. H shows clustering results in this regime are initial-state
    independent, so random seeding matches k-means++ quality at far lower
    cost; seeding strategies are explicitly out of the paper's scope (§I).
    """
    pick = seed_rows(docs.n_docs, k, seed=seed)
    sel = SparseDocs(ids=docs.ids[pick], vals=docs.vals[pick], nnz=docs.nnz[pick], dim=docs.dim)
    means = seed_centroids(sel, k)
    index = build_mean_index(means, params)
    n = docs.n_docs
    return KMeansState(
        index=index,
        assign=jnp.zeros((n,), jnp.int32),
        rho_self=jnp.full((n,), -jnp.inf, jnp.float32),
        rho_self_prev=jnp.full((n,), -jnp.inf, jnp.float32),
        iteration=jnp.asarray(0, jnp.int32),
        ub=jnp.full((n, n_ub_groups(k)), jnp.inf, jnp.float32),
    )


def init_state_from_store(store, k: int, params: StructuralParams, *,
                          seed: int = 0) -> KMeansState:
    """:func:`init_state` for an out-of-core corpus: the same PRNG draw and
    the same centroid construction, but the K seed rows are gathered from
    the store's chunks (a host gather touching only their chunks) and the
    per-document arrays cover every store row — real rows start at
    ρ_self = -inf, the dead tail rows at the repo-wide pad value 0."""
    import numpy as np

    pick = seed_rows(store.n_docs, k, seed=seed)
    sel = store.gather_rows(np.asarray(pick))
    index = build_mean_index(seed_centroids(sel, k), params)
    n_rows = store.n_rows
    valid = jnp.arange(n_rows) < store.n_docs
    rho0 = jnp.where(valid, -jnp.inf, 0.0).astype(jnp.float32)
    return KMeansState(
        index=index,
        assign=jnp.zeros((n_rows,), jnp.int32),
        rho_self=rho0,
        rho_self_prev=rho0,
        iteration=jnp.asarray(0, jnp.int32),
        # Dead tail rows get ub = 0 (the ρ_self pad convention's twin):
        # their bound drifting is harmless (zero counts), and a finite pad
        # keeps the padded state free of inf-arithmetic surprises.
        ub=jnp.broadcast_to(
            jnp.where(valid, jnp.inf, 0.0).astype(jnp.float32)[:, None],
            (n_rows, n_ub_groups(k))),
    )

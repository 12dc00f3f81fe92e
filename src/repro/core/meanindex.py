"""Structured mean-inverted index (paper §IV-A, Fig. 5/6) — TPU adaptation.

The paper's index is a ragged array of postings ξ_s per term, partitioned by
two shared structural parameters into

    Region 1:  s <  t_th                      (exact, short postings)
    Region 2:  s >= t_th and v >= v_th        (exact, the VMEM-hot block)
    Region 3:  s >= t_th and v <  v_th        (upper-bounded by y * v_th)

On TPU we keep the *transposed dense* mean matrix ``means_t (D, K)`` — row s
is exactly the posting list ξ_s in full expression (the paper's own M^p uses
full expression for O(1) centroid addressing).  Regions are realised as
masks/counts over this matrix, so the three-region logic is branch-free:
shared (t_th, v_th) thresholds become uniform select masks — the TPU analogue
of the paper's "no irregular conditional branches".
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# Block-vector sketch width (Knittel, Koch & Ertl, arxiv_2108.00895): the
# vocabulary is split into S contiguous groups of g dimensions and each
# vector is summarised by the per-group L2 norms.  For non-negative data
# every group satisfies Cauchy-Schwarz, so the S-dim dense dot of two
# sketches upper-bounds the exact D-dim dot — a sound pre-filter for the
# sparse_sim pass.  S is capped at SKETCH_DIM so the sketch similarity is
# a tiny dense matmul regardless of vocabulary size.
SKETCH_DIM = 64


def sketch_group_width(dim: int) -> int:
    """Group width g so that ceil(dim / g) <= SKETCH_DIM."""
    return -(-dim // SKETCH_DIM)


def sketch_size(dim: int) -> int:
    """Number of sketch slots S = ceil(dim / g) (<= SKETCH_DIM)."""
    g = sketch_group_width(dim)
    return -(-dim // g)


def sketch_means(means_t: jax.Array) -> jax.Array:
    """(D, K) transposed means -> (S, K) block-vector sketch.

    Slot s holds the L2 norm of rows [s*g, (s+1)*g) of means_t per centroid.
    """
    d = means_t.shape[0]
    g = sketch_group_width(d)
    s = sketch_size(d)
    seg = jnp.arange(d, dtype=jnp.int32) // g
    sq = jax.ops.segment_sum(means_t * means_t, seg, num_segments=s)
    return jnp.sqrt(sq)


def doc_sketch(ids: jax.Array, vals: jax.Array, dim: int) -> jax.Array:
    """(B, P) padded sparse docs -> (B, S) block-vector sketch.

    Dead slots carry val 0 and contribute nothing regardless of their id,
    so the padding convention needs no special-casing.  Shared verbatim by
    both backends so the sketches are bitwise identical across them.
    """
    g = sketch_group_width(dim)
    s = sketch_size(dim)
    seg = jnp.clip(ids.astype(jnp.int32) // g, 0, s - 1)
    sq = jax.vmap(
        lambda sg, v: jax.ops.segment_sum(v * v, sg, num_segments=s)
    )(seg, vals)
    return jnp.sqrt(sq)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class StructuralParams:
    """Shared thresholds (t_th, v_th) — paper Table III."""

    t_th: jax.Array  # () int32 — term-ID threshold (df-rank space)
    v_th: jax.Array  # () float32 — mean-feature-value threshold

    def tree_flatten(self):
        return (self.t_th, self.v_th), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(*leaves)

    @staticmethod
    def trivial(dim: int) -> "StructuralParams":
        """t_th = 0, v_th = 1: Region 1 empty, Region 2 empty — degenerates to
        a pure L1 bound (the ThT ablation of App. D)."""
        return StructuralParams(t_th=jnp.asarray(0, jnp.int32), v_th=jnp.asarray(1.0, jnp.float32))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class MeanIndex:
    """Mean set + the derived statistics every filter needs.

    means_t: (D, K) float32 — transposed means; row s = posting list ξ_s.
    mf:      (D,) int32     — mean frequency of term s (nonzeros in row s).
    moving:  (K,) bool      — centroid moved at the last update (ICP state).
    n_moving:() int32       — number of moving centroids (nMv).
    params:  StructuralParams.
    mf_h:    (D,) int32     — (mfH)_s: entries with v >= v_th (Region-2 width).
    sketch_t:(S, K) float32 — block-vector sketch of the means (sketch modes).
    """

    means_t: jax.Array
    mf: jax.Array
    moving: jax.Array
    n_moving: jax.Array
    params: StructuralParams
    mf_h: jax.Array
    sketch_t: jax.Array

    def tree_flatten(self):
        return (self.means_t, self.mf, self.moving, self.n_moving,
                self.params, self.mf_h, self.sketch_t), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(*leaves)

    @property
    def dim(self) -> int:
        return self.means_t.shape[0]

    @property
    def k(self) -> int:
        return self.means_t.shape[1]

    def region2_mask(self) -> jax.Array:
        """(D, K) bool — Region-2 membership."""
        s_tail = jnp.arange(self.dim)[:, None] >= self.params.t_th
        return s_tail & (self.means_t >= self.params.v_th)

    def with_params(self, params: StructuralParams) -> "MeanIndex":
        return index_from_means_t(self.means_t, params, moving=self.moving)


def _mf_counts(means_t: jax.Array) -> jax.Array:
    return jnp.sum(means_t > 0, axis=1).astype(jnp.int32)


def build_mean_index(means: jax.Array, params: StructuralParams,
                     moving: jax.Array | None = None) -> MeanIndex:
    """means: (K, D) L2-normalised centroid matrix -> MeanIndex.

    The paper's update step (Alg. 6 steps 3–5) constructs ξ_s arrays and the
    moving/invariant block split; here both collapse to cheap column stats
    because the index is dense-blocked (DESIGN.md §2).
    """
    k, d = means.shape
    means_t = means.T
    mf = _mf_counts(means_t)
    if moving is None:
        moving = jnp.ones((k,), bool)
    mf_h = jnp.sum((means_t >= params.v_th)
                   & (jnp.arange(d)[:, None] >= params.t_th), axis=1).astype(jnp.int32)
    return MeanIndex(
        means_t=means_t,
        mf=mf,
        moving=moving,
        n_moving=jnp.sum(moving).astype(jnp.int32),
        params=params,
        mf_h=mf_h,
        sketch_t=sketch_means(means_t),
    )


def _index_from_means_t(means_t, params, moving):
    return build_mean_index(means_t.T, params, moving=moving)


_INDEX_FROM_MEANS_T = {False: jax.jit(_index_from_means_t),
                       True: jax.jit(_index_from_means_t, donate_argnums=0)}


def index_from_means_t(means_t: jax.Array, params: StructuralParams,
                       moving: jax.Array | None = None, *,
                       donate: bool = False) -> MeanIndex:
    """:func:`build_mean_index` from the transposed (D, K) means, as one
    compiled program: the two transposes cancel instead of each
    materialising a copy of the index (4.95 GB per chip at nyt1m's width).
    ``donate=True`` hands ``means_t`` over to the new index (the caller's
    array is consumed), so no second copy of it is ever live."""
    if moving is None:
        moving = jnp.ones((means_t.shape[1],), bool)
    donate = donate and jax.default_backend() != "cpu"   # CPU cannot alias
    return _INDEX_FROM_MEANS_T[donate](means_t, params, moving)


def normalized_means(lam: jax.Array, fallback_means_t: jax.Array) -> jax.Array:
    """(K, D) unit-norm means from cluster sums λ (Alg. 6 step 2→3).

    Empty clusters keep their previous mean (still a unit vector) so the
    exactness property vs Lloyd from identical states is preserved.  Shared
    by the single-device update step, the shard-local distributed update,
    and the serving engine's index rebuild.
    """
    norms = jnp.sqrt(jnp.sum(lam * lam, axis=1, keepdims=True))
    empty = norms[:, 0] == 0.0
    fallback = fallback_means_t.T.astype(jnp.float32)
    return jnp.where(empty[:, None], fallback, lam / jnp.maximum(norms, 1e-12))


def mean_value_stats(means_t: jax.Array, t_th: jax.Array):
    """Row statistics used by EstParams:

    col_sum:  (D,)  Σ_k v_{s,k}         (Eq. 32 inner sum)
    Returns (col_sum,).
    """
    return (jnp.sum(means_t, axis=1),)


def delta_v_bar(means_t: jax.Array, v_grid: jax.Array) -> jax.Array:
    """Δv̄_{s,h} = (1/K) Σ_k relu(v_h − v_{s,k})  — Eq. (39).

    Includes absent centroids (v = 0), matching the (K − mf_s)·v_h term.
    Returns (D, H) float32.
    """
    def per_h(v_h):
        return jnp.mean(jnp.maximum(v_h - means_t, 0.0), axis=1)

    # One candidate at a time: a vmap would materialise (H, D, K) — 55 GB
    # at D=141,043, K=4096, H=24 — where a map needs one (D, K) temporary.
    return jax.lax.map(per_h, v_grid).T


def mfh_table(means_t: jax.Array, v_grid: jax.Array) -> jax.Array:
    """(mfH)_{s,h} for every v_th candidate — (D, H) int32 (Eq. 9)."""

    def per_h(v_h):
        return jnp.sum(means_t >= v_h, axis=1).astype(jnp.int32)

    return jax.lax.map(per_h, v_grid).T      # one (D, K) temporary at a time

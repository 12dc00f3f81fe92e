"""Dense block-vector sketch similarity kernel (sketch pre-filter pass).

The sketch gate (DESIGN.md §11) reduces every doc and mean to an S-dim
block-vector of group L2 norms (S <= meanindex.SKETCH_DIM), so the gating
similarity is a tiny dense matmul: (B, S) @ (S, K).  One grid axis over B
tiles; S and K ride whole in each block (S is at most 64, padded to the
128-lane tile by the ops wrapper with zeros, which leave the dot product
bit-identical to the unpadded reference matmul).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sketch_kernel(x_ref, m_ref, o_ref):
    # HIGHEST: these sums are upper bounds that pruning trusts; a bf16
    # rounding of either operand could push one below the exact cosine.
    o_ref[...] = jnp.dot(x_ref[...], m_ref[...],
                         preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("b_blk", "interpret"))
def sketch_sim_pallas(sk_docs, sketch_t, *, b_blk: int = 128,
                      interpret: bool = True):
    """(B, S) doc sketches × (S, K) mean sketches -> (B, K) sketch bounds.

    B must be a multiple of b_blk; S and K must be lane-aligned (the ops
    wrapper pads with zeros, which do not perturb the dot product).
    """
    b, s = sk_docs.shape
    s2, k = sketch_t.shape
    assert s == s2, (s, s2)
    assert b % b_blk == 0, (b, b_blk)

    grid = (b // b_blk,)
    return pl.pallas_call(
        _sketch_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b_blk, s), lambda i: (i, 0)),
            pl.BlockSpec((s, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((b_blk, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, k), jnp.float32),
        interpret=interpret,
    )(sk_docs, sketch_t)

"""The plain reference: exact spherical k-means in jax.numpy.

Lloyd's algorithm as the paper states it, written without any of the
program's code or its pruning: every document is scored against every mean,
a document moves only to a strictly better mean (ties keep the current one,
and among equal improvers the lowest id wins), each mean is the normalised
sum of its documents, and an empty cluster keeps its mean.  Seeding takes K
distinct documents with ``jax.random.choice(PRNGKey(seed), N, (K,),
replace=False)`` as unit means, the draw the configuration states.

Every product is an elementwise float32 multiply followed by a float32
reduction; nothing goes through a matrix unit, so no matmul precision
setting can lower it.  ``store`` is the type the means and the document
values are kept in: float32 for the reference, bfloat16 for the control that
must fail the comparison.  Scoring runs in row blocks so that a block's
gathered (B, P, K) slab stays under a fixed size.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BLOCK_BYTES = 512 << 20      # gathered (B, P, K) float32 slab per block


@dataclasses.dataclass
class Fit:
    """What a fit gives: final labels (host), final (D, K) means (device)
    and the objective J = sum_i <x_i, mu_a(i)> after each iteration."""
    labels: np.ndarray
    means_t: jax.Array
    objectives: list


def block_rows(pad_width: int, k: int, n: int) -> int:
    """Rows per scoring block: a power of two, at most ``n`` rounded up."""
    b = max(1, BLOCK_BYTES // (pad_width * k * 4))
    b = 1 << (b.bit_length() - 1)
    return max(1, min(b, 1 << max(0, (n - 1).bit_length())))


def _live(ids, nnz):
    return jnp.arange(ids.shape[1])[None, :] < nnz[:, None]


@partial(jax.jit, static_argnames=("k", "dim", "store"))
def _seed_means(ids, vals, nnz, rows, *, k: int, dim: int, store):
    """(D, K) unit means from the K seed documents."""
    vals = jnp.where(_live(ids, nnz), vals, 0.0)
    sel_ids, sel_vals = ids[rows], vals[rows]                 # (K, P)
    cols = jnp.broadcast_to(jnp.arange(k)[:, None], sel_ids.shape)
    m = jnp.zeros((dim, k), jnp.float32).at[sel_ids, cols].add(
        sel_vals)
    m = m / jnp.maximum(jnp.sqrt(jnp.sum(m * m, axis=0)), 1e-12)[None, :]
    return m.astype(store)


@partial(jax.jit, static_argnames=("block",))
def _score(ids, vals, nnz, means_t, *, block: int):
    """Per row: the best mean (lowest id among equals) and its similarity."""
    n, p = ids.shape
    vals = jnp.where(_live(ids, nnz), vals, 0).astype(jnp.float32)
    nb = n // block
    resh = lambda a: a.reshape((nb, block) + a.shape[1:])

    def one(args):
        bi, bv = args
        rows = means_t[bi].astype(jnp.float32)                # (B, P, K)
        sims = jnp.sum(bv[:, :, None] * rows, axis=1)         # (B, K)
        j = jnp.argmax(sims, axis=1).astype(jnp.int32)
        best = jnp.take_along_axis(sims, j[:, None], axis=1)[:, 0]
        return j, best

    j, best = lax.map(one, (resh(ids), resh(vals)))
    return j.reshape(n), best.reshape(n)


@partial(jax.jit, static_argnames=("store",))
def _update(ids, vals, nnz, labels, means_t, *, store):
    """Normalised cluster sums; an empty cluster keeps its mean."""
    vals = jnp.where(_live(ids, nnz), vals, 0).astype(jnp.float32)
    sums = jnp.zeros(means_t.shape, jnp.float32).at[
        ids, labels[:, None]].add(vals)
    norms = jnp.sqrt(jnp.sum(sums * sums, axis=0))
    new = sums / jnp.maximum(norms, 1e-12)[None, :]
    return jnp.where((norms == 0.0)[None, :], means_t.astype(jnp.float32),
                     new).astype(store)


@jax.jit
def _self_sims(ids, vals, nnz, labels, means_t):
    vals = jnp.where(_live(ids, nnz), vals, 0).astype(jnp.float32)
    picked = means_t[ids, labels[:, None]].astype(jnp.float32)
    return jnp.sum(vals * picked, axis=1)


def _pad(a, n_to: int):
    pad = n_to - a.shape[0]
    if pad == 0:
        return a
    return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))


def score(ids, vals, nnz, means_t):
    """(best id, best similarity) of every row."""
    n, p = ids.shape
    b = block_rows(p, means_t.shape[1], n)
    n_to = -(-n // b) * b
    j, best = _score(_pad(jnp.asarray(ids), n_to),
                     _pad(jnp.asarray(vals), n_to),
                     _pad(jnp.asarray(nnz), n_to), means_t, block=b)
    return j[:n], best[:n]


def means_from_labels(ids, vals, nnz, labels, k: int, dim: int):
    """(D, K) float32 normalised cluster sums of ``labels``; empty clusters
    come out as zero columns."""
    return _update(ids, vals, nnz, jnp.asarray(labels, jnp.int32),
                   jnp.zeros((dim, k), jnp.float32), store=jnp.float32)


def seed_rows(n_docs: int, k: int, seed: int):
    return jax.random.choice(jax.random.PRNGKey(seed), n_docs, shape=(k,),
                             replace=False)


def lloyd(ids, vals, nnz, *, k: int, dim: int, seed: int, max_iter: int,
          store=jnp.float32) -> Fit:
    """Lloyd from the seeded means for ``max_iter`` iterations, or through
    the first iteration in which no label changes."""
    ids, nnz = jnp.asarray(ids), jnp.asarray(nnz)
    vals = jnp.asarray(vals).astype(store)
    n = ids.shape[0]
    means_t = _seed_means(ids, vals, nnz, seed_rows(n, k, seed), k=k,
                          dim=dim, store=store)
    labels = jnp.zeros((n,), jnp.int32)
    rho = jnp.full((n,), -jnp.inf, jnp.float32)
    objectives = []
    for _ in range(max_iter):
        j, best = score(ids, vals, nnz, means_t)
        new = jnp.where(best > rho, j, labels)
        changed = int(jnp.sum(new != labels))
        labels = new
        means_t = _update(ids, vals, nnz, labels, means_t, store=store)
        rho = _self_sims(ids, vals, nnz, labels, means_t)
        objectives.append(float(np.sum(np.asarray(rho, np.float64))))
        if changed == 0:
            break
    return Fit(labels=np.asarray(labels), means_t=means_t,
               objectives=objectives)

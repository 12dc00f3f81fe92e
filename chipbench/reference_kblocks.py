"""The plain reference (``chipbench/reference.py``) with its means in K-blocks.

At a K whose (D, K) float32 means no one device holds, the means are split
into as many blocks of consecutive ids as there are devices given, and each
block lives on its own device (``jax.device_put``), beside a copy of the
corpus.  Each step is the whole reference's, run per block with its jitted
helpers:

- scoring: every block gives each row its best similarity and the local id
  of that mean; the blocks are combined in order with a strict ``>``, so
  the lowest id among equals wins, as in the whole reference;
- update: each block sums only the rows labelled into its range; the other
  rows' values are zeroed, so they add exact zeros, and an empty cluster
  keeps its mean;
- ρ_self: each row's similarity to its own mean, taken from the block that
  owns its label; the objective sums it in float64 on the host.

Seeding draws the K seed rows as the whole reference does and gives block
``b`` rows ``b*K_b`` to ``(b+1)*K_b``.  ``store`` is the type the means and
document values are kept in, as in ``reference.lloyd``: float32 for the
reference, bfloat16 for the control.  No program code is imported.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference


@dataclasses.dataclass
class Fit:
    """Final labels (host), the final means as (D, K_b) blocks, one per
    device, and the objective after each iteration."""
    labels: np.ndarray
    blocks: list
    objectives: list


def _donating(fn, argnum: int):
    """``fn`` (a jitted helper) with its ``argnum`` argument donated where
    the backend can alias buffers."""
    if jax.default_backend() == "cpu":
        return fn
    return jax.jit(fn, static_argnames=("store",), donate_argnums=(argnum,))


@jax.jit
def _block_gap(ids, vals, nnz, local, means_b):
    """Per mean of one block: the largest difference between ``means_b``
    and the normalised sum of the rows whose ``local`` label is that mean
    (rows of other blocks carry ``local`` = K_b and add nothing), with the
    column's squared norm: a zero norm marks a column no row fills."""
    k_b = means_b.shape[1]
    vals = jnp.where(reference._live(ids, nnz) & (local < k_b)[:, None],
                     vals, 0).astype(jnp.float32)
    sums = jnp.zeros(means_b.shape, jnp.float32).at[
        ids, jnp.minimum(local, k_b - 1)[:, None]].add(vals)
    norms = jnp.sqrt(jnp.sum(sums * sums, axis=0))
    own = sums / jnp.maximum(norms, 1e-12)[None, :]
    return jnp.max(jnp.abs(means_b.astype(jnp.float32) - own), axis=0), norms


class Blocks:
    """A corpus copied onto each device of ``devices``, one K-block each."""

    def __init__(self, ids, vals, nnz, *, k: int, devices, store=jnp.float32):
        if k % len(devices):
            raise ValueError(f"K={k} does not split into {len(devices)} "
                             f"blocks")
        self.k, self.k_b, self.devices = k, k // len(devices), list(devices)
        self.n = int(np.shape(nnz)[0])
        host = [np.asarray(ids), np.asarray(jnp.asarray(vals).astype(store)),
                np.asarray(nnz)]
        self.docs = [tuple(jax.device_put(a, d) for a in host)
                     for d in self.devices]

    def local(self, labels: np.ndarray, b: int) -> np.ndarray:
        """Block ``b``'s local ids of ``labels``, K_b where another block
        owns the label."""
        lb = np.asarray(labels, np.int64) - b * self.k_b
        return np.where((lb >= 0) & (lb < self.k_b), lb, self.k_b).astype(
            np.int32)

    def score(self, blocks: list):
        """(best id, best similarity) of every row over all blocks."""
        parts = [reference.score(*self.docs[b], m)
                 for b, m in enumerate(blocks)]
        best = np.full((self.n,), -np.inf, np.float32)
        j = np.zeros((self.n,), np.int32)
        for b, (jb, sb) in enumerate(parts):
            jb, sb = np.asarray(jb), np.asarray(sb)
            better = sb > best
            best = np.where(better, sb, best)
            j = np.where(better, jb + b * self.k_b, j).astype(np.int32)
        return j, best

    def gap(self, labels: np.ndarray, blocks: list) -> float:
        """The update check of ``check.fit_numbers`` block by block: the
        largest difference, over the means ``labels`` fill, between
        ``blocks`` and the normalised sums of the rows labelled into them."""
        filled = np.bincount(labels, minlength=self.k) > 0
        gaps = [_block_gap(*self.docs[b],
                           jax.device_put(self.local(labels, b),
                                          self.devices[b]), m)
                for b, m in enumerate(blocks)]
        out = 0.0
        for b, (diff, _) in enumerate(gaps):
            f = filled[b * self.k_b:(b + 1) * self.k_b]
            out = max(out, float(np.max(np.where(f, np.asarray(diff), 0.0),
                                        initial=0.0)))
        return out


def lloyd(ids, vals, nnz, *, k: int, dim: int, seed: int, max_iter: int,
          devices, store=jnp.float32) -> Fit:
    """``reference.lloyd`` with the means in ``len(devices)`` K-blocks."""
    bl = Blocks(ids, vals, nnz, k=k, devices=devices, store=store)
    n, k_b = bl.n, bl.k_b
    update = _donating(reference._update, 4)
    rows = np.asarray(reference.seed_rows(n, k, seed))
    blocks = [reference._seed_means(
        *bl.docs[b], jax.device_put(rows[b * k_b:(b + 1) * k_b], d),
        k=k_b, dim=dim, store=store) for b, d in enumerate(bl.devices)]
    labels = np.zeros((n,), np.int32)
    rho = np.full((n,), -np.inf, np.float32)
    objectives = []
    for _ in range(max_iter):
        j, best = bl.score(blocks)
        new = np.where(best > rho, j, labels).astype(np.int32)
        changed = int(np.sum(new != labels))
        labels = new
        owner = labels // k_b
        rho = np.zeros((n,), np.float32)
        parts = []
        for b, d in enumerate(bl.devices):
            ids_b, vals_b, nnz_b = bl.docs[b]
            local = bl.local(labels, b)
            mine = jax.device_put(local < k_b, d)
            lab = jax.device_put(np.minimum(local, k_b - 1), d)
            blocks[b] = update(ids_b, jnp.where(mine[:, None], vals_b, 0),
                               nnz_b, lab, blocks[b], store=store)
            parts.append(reference._self_sims(ids_b, vals_b, nnz_b, lab,
                                              blocks[b]))
        for b, part in enumerate(parts):
            rho = np.where(owner == b, np.asarray(part), rho)
        objectives.append(float(np.sum(rho.astype(np.float64))))
        if changed == 0:
            break
    return Fit(labels=labels, blocks=blocks, objectives=objectives)

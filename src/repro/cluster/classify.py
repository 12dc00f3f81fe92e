"""The fused classification path every runtime shares.

``SphericalKMeans.predict``, ``FittedModel.predict``, and
``serve.ClusterEngine.classify`` all route through :func:`classify_docs`:
one jitted ``lax.map`` epoch over padded batches, exact similarities from
the pluggable backend (core/backends.py), top-1 on device, one device→host
sync per request.  A parity bug can therefore only exist in one place.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("backend", "bs", "dim"))
def _classify_fused(backend: str, ids, vals, nnz, dim: int, index, bs: int):
    """Fused classification epoch: lax.map over reshaped batches, exact
    similarities from the chosen backend, top-1 on device."""
    from repro.sparse import SparseDocs
    from repro.core.backends import resolve_backend

    bk = resolve_backend(backend)
    n = ids.shape[0]
    nb = n // bs
    resh = lambda a: a.reshape((nb, bs) + a.shape[1:])

    def batch_fn(args):
        bids, bvals, bnnz = args
        bdocs = SparseDocs(ids=bids, vals=bvals, nnz=bnnz, dim=dim)
        out = bk.accumulate(bdocs, index, jnp.zeros((bs,), bool), mode="exact",
                            diag=False)   # serving never reads Mult
        sims = out["sims"]
        best = jnp.argmax(sims, axis=1).astype(jnp.int32)
        return best, jnp.take_along_axis(sims, best[:, None], axis=1)[:, 0]

    a, s = jax.lax.map(batch_fn, (resh(ids), resh(vals), resh(nnz)))
    return a.reshape(n), s.reshape(n)


@partial(jax.jit, static_argnames=("backend", "bs", "dim"))
def _transform_fused(backend: str, ids, vals, nnz, dim: int, index, bs: int):
    """Fused similarity epoch: the full (N, K) cosine matrix vs the index."""
    from repro.sparse import SparseDocs
    from repro.core.backends import resolve_backend

    bk = resolve_backend(backend)
    n = ids.shape[0]
    nb = n // bs
    resh = lambda a: a.reshape((nb, bs) + a.shape[1:])

    def batch_fn(args):
        bids, bvals, bnnz = args
        bdocs = SparseDocs(ids=bids, vals=bvals, nnz=bnnz, dim=dim)
        return bk.accumulate(bdocs, index, jnp.zeros((bs,), bool),
                             mode="exact", diag=False)["sims"]

    s = jax.lax.map(batch_fn, (resh(ids), resh(vals), resh(nnz)))
    return s.reshape(n, -1)


@partial(jax.jit, static_argnames=("backend", "bs", "dim", "cmax", "n_probe"))
def _routed_fused(backend: str, ids, vals, nnz, dim: int, coarse_index,
                  means_ext, starts, sizes, bs: int, cmax: int, n_probe: int):
    """Coarse-routed classification epoch (two-level IVF — DESIGN.md §13).

    Per batch: (1) score the K_c coarse means through the pluggable backend
    (exactly the flat epoch at K = K_c); (2) ``lax.top_k`` the ``n_probe``
    best cells; (3) score ONLY those cells' fine means with a gather-TAAT
    scan over the P tuple slots — each step is one ``(bs, J)`` 2-D gather
    from the sentinel-extended ``means_ext (D, K_eff + 1)`` at the candidate
    columns, ``J = n_probe * cmax``, so per-object work is K_c + Σ probed
    cell sizes instead of K_eff.

    Exactness: the scan accumulates ``vals[:, p] * means_ext[ids[:, p],
    col]`` in ascending-p order — element-for-element the same float32
    additions, in the same order, as the reference flat TAAT scan
    (``core.backends.reference_scan``, at any fold) performs for those
    columns.  When the routed candidate set contains the true argmax (always
    at n_probe = K_c; measured as recall@1 below it), the winning similarity
    is therefore *bitwise* equal to the flat path's.

    Dead candidate slots (past a cell's size) point at the all-zero sentinel
    column K_eff and are masked to -inf before the argmax; dead *rows*
    (nnz = 0 tail padding) follow the repo-wide ρ_self = 0 convention and
    are trimmed by callers.  Returns (assign, best-sim, scored) where
    ``scored`` is the per-object count of centroids scored (K_c + Σ probed
    sizes) — the Mult-counter hook the IVF benchmark and tests assert on.
    """
    from repro.sparse import SparseDocs
    from repro.core.backends import resolve_backend

    bk = resolve_backend(backend)
    n = ids.shape[0]
    nb = n // bs
    k_c = starts.shape[0]
    k_eff = means_ext.shape[1] - 1
    resh = lambda a: a.reshape((nb, bs) + a.shape[1:])
    slot = jnp.arange(cmax, dtype=jnp.int32)

    def batch_fn(args):
        bids, bvals, bnnz = args
        bdocs = SparseDocs(ids=bids, vals=bvals, nnz=bnnz, dim=dim)
        csims = bk.accumulate(bdocs, coarse_index, jnp.zeros((bs,), bool),
                              mode="exact", diag=False)["sims"]
        _, cells = jax.lax.top_k(csims, n_probe)          # (bs, n_probe)
        psizes = sizes[cells]                             # (bs, n_probe)
        cols = starts[cells][:, :, None] + slot[None, None, :]
        cols = jnp.where(slot[None, None, :] < psizes[:, :, None],
                         cols, k_eff).reshape(bs, n_probe * cmax)

        def p_step(sims, xs):
            idp, vp = xs                                  # (bs,), (bs,)
            return sims + vp[:, None] * means_ext[idp[:, None], cols], None

        sims, _ = jax.lax.scan(
            p_step, jnp.zeros((bs, n_probe * cmax), jnp.float32),
            (bids.T, bvals.T))
        sims = jnp.where(cols == k_eff, -jnp.inf, sims)
        bestj = jnp.argmax(sims, axis=1)
        assign = jnp.take_along_axis(cols, bestj[:, None], 1)[:, 0]
        best = jnp.take_along_axis(sims, bestj[:, None], 1)[:, 0]
        scored = (k_c + jnp.sum(psizes, axis=1)).astype(jnp.int32)
        return assign.astype(jnp.int32), best, scored

    a, s, sc = jax.lax.map(batch_fn, (resh(ids), resh(vals), resh(nnz)))
    return a.reshape(n), s.reshape(n), sc.reshape(n)


def classify_docs_routed(model, docs, *, n_probe: int | None = None,
                         backend: str | None = None, batch_size: int = 4096,
                         with_stats: bool = False):
    """docs vs a two-level model -> (assign, sims[, scored]) — the routed
    ANN classify.

    ``model`` is a :class:`repro.cluster.model.TwoLevelFittedModel` (duck-
    typed: anything with ``_routed_operands()`` / ``index`` / ``coarse_k``).
    ``assign`` is in the GLOBAL fine-label space (same ids as the flat
    path over ``model.index``).  ``n_probe`` defaults to the model's
    setting; ``n_probe >= K_c`` probes every cell and delegates to the flat
    :func:`classify_docs` — provably exact and bitwise-identical to the
    flat path on every backend, since it IS the flat path.  With
    ``with_stats=True`` also returns ``scored`` (N,) int32 — centroids
    scored per object (K_c + Σ probed cell sizes; K_eff when delegating).

    Accepts a resident SparseDocs or an out-of-core DocStore (chunk-
    streamed like :func:`classify_docs`).
    """
    from repro.sparse import pad_rows
    from repro.sparse.store import ChunkPrefetcher, DocStore

    backend = model.backend if backend is None else backend
    n_probe = model.n_probe if n_probe is None else int(n_probe)
    k_c = model.coarse_k
    if not 1 <= n_probe <= k_c:
        raise ValueError(f"n_probe must be in [1, coarse_k={k_c}], "
                         f"got {n_probe}")
    if n_probe >= k_c:          # probe everything == the flat scan
        a, s = classify_docs(model.index, docs, backend=backend,
                             batch_size=batch_size)
        if not with_stats:
            return a, s
        return a, s, np.full(a.shape, model.index.k, np.int32)

    coarse_index, means_ext, starts, sizes, cmax = model._routed_operands()

    def run(ids, vals, nnz, dim, bs):
        return _routed_fused(backend, ids, vals, nnz, dim, coarse_index,
                             means_ext, starts, sizes, bs, cmax, n_probe)

    if isinstance(docs, DocStore):
        store = docs
        bs, padder = _store_tiles(store, batch_size)
        parts = ([], [], [])
        for ci, cdocs in ChunkPrefetcher(store):
            cdocs = padder(cdocs)
            out = run(cdocs.ids, cdocs.vals, cdocs.nnz, store.dim, bs)
            for part, arr in zip(parts, out):
                part.append(np.asarray(arr)[:store.chunk_size])
        a, s, sc = (np.concatenate(p)[:store.n_docs] for p in parts)
        return (a, s, sc) if with_stats else (a, s)

    n = docs.n_docs
    if n == 0:
        out = (np.zeros((0,), np.int32), np.zeros((0,), np.float32),
               np.zeros((0,), np.int32))
        return out if with_stats else out[:2]
    bs = min(batch_size, n)
    pdocs = pad_rows(docs, bs)
    a, s, sc = run(pdocs.ids, pdocs.vals, pdocs.nnz, pdocs.dim, bs)
    out = (np.asarray(a)[:n], np.asarray(s)[:n], np.asarray(sc)[:n])
    return out if with_stats else out[:2]


def _store_tiles(store, batch_size: int):
    """(tile size, per-chunk padder) for scanning a store's (C, P) chunks —
    the SAME tile policy as the streaming fit (core/lloyd._tile_bs): an
    unaligned chunk is padded with dead rows (inert by the ρ_self = 0
    convention) rather than shrinking the tile, and callers trim per-chunk
    outputs back to C."""
    from repro.core.lloyd import _tile_bs
    from repro.sparse import pad_rows

    bs = _tile_bs(store.chunk_size, batch_size)
    padder = ((lambda d: pad_rows(d, bs)) if store.chunk_size % bs
              else (lambda d: d))
    return bs, padder


def classify_docs(index, docs, *, backend: str = "auto",
                  batch_size: int = 4096):
    """docs vs a frozen MeanIndex -> (assign (N,) int32, sims (N,) float32).

    ``docs`` may be a resident SparseDocs or an out-of-core DocStore: store
    chunks stream through the double-buffered prefetcher and the SAME fused
    per-chunk epoch, so serving stays chunk-for-chunk identical to the
    resident path (parity-tested).
    """
    from repro.sparse import pad_rows
    from repro.sparse.store import ChunkPrefetcher, DocStore

    if isinstance(docs, DocStore):
        store = docs
        bs, padder = _store_tiles(store, batch_size)
        parts_a, parts_s = [], []
        for ci, cdocs in ChunkPrefetcher(store):
            cdocs = padder(cdocs)
            a, s = _classify_fused(backend, cdocs.ids, cdocs.vals, cdocs.nnz,
                                   store.dim, index, bs)
            parts_a.append(np.asarray(a)[:store.chunk_size])
            parts_s.append(np.asarray(s)[:store.chunk_size])
        return (np.concatenate(parts_a)[:store.n_docs],
                np.concatenate(parts_s)[:store.n_docs])

    n = docs.n_docs
    if n == 0:
        return (np.zeros((0,), np.int32), np.zeros((0,), np.float32))
    bs = min(batch_size, n)
    pdocs = pad_rows(docs, bs)
    a, s = _classify_fused(backend, pdocs.ids, pdocs.vals, pdocs.nnz,
                           pdocs.dim, index, bs)
    return np.asarray(a)[:n], np.asarray(s)[:n]


def transform_docs(index, docs, *, backend: str = "auto",
                   batch_size: int = 4096):
    """docs vs a frozen MeanIndex -> dense (N, K) cosine similarities.

    Accepts a DocStore like :func:`classify_docs` (chunk-streamed)."""
    from repro.sparse import pad_rows
    from repro.sparse.store import ChunkPrefetcher, DocStore

    if isinstance(docs, DocStore):
        store = docs
        bs, padder = _store_tiles(store, batch_size)
        parts = []
        for ci, cdocs in ChunkPrefetcher(store):
            cdocs = padder(cdocs)
            parts.append(np.asarray(_transform_fused(
                backend, cdocs.ids, cdocs.vals, cdocs.nnz, store.dim,
                index, bs))[:store.chunk_size])
        return np.concatenate(parts)[:store.n_docs]

    n = docs.n_docs
    if n == 0:
        return np.zeros((0, index.k), np.float32)
    bs = min(batch_size, n)
    pdocs = pad_rows(docs, bs)
    s = _transform_fused(backend, pdocs.ids, pdocs.vals, pdocs.nnz,
                         pdocs.dim, index, bs)
    return np.asarray(s)[:n]

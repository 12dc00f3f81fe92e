"""Pluggable execution strategies: one estimator, several runtimes.

A strategy turns (docs, ClusterConfig) into a :class:`LloydResult`; the
estimator wraps that into the :class:`FittedModel` artifact.  Every built-in
strategy runs the *same* algorithm and the *same* backend accumulators
(core/backends.py) — they differ only in where the arrays live:

``single_host``
    The fused on-device Lloyd fit (core/lloyd.py): one jitted while_loop,
    O(1) host syncs per fit.  Requires the corpus resident on device.

``streaming``
    The out-of-core chunk-scan fit (core/lloyd.streaming_fit) over a
    :class:`repro.sparse.DocStore`: chunks stream host→device through the
    double-buffered prefetcher, O(1) host syncs per epoch, resumable from
    mid-epoch checkpoints.  Selected by passing a DocStore to ``fit`` or
    by ``ClusterConfig(algo_mode='minibatch')``.

``mesh``
    The pod-mesh loop (distributed/kmeans.py): objects sharded over the
    object axes, the mean-inverted index over 'model', shard-local
    accumulators from the shared backend protocol, one (max, argmin-id)
    all-reduce per assignment.  Selected by ``ClusterConfig(mesh=...)``;
    also accepts a DocStore (chunks stream into the sharded object arrays).

``two_level``
    The nested IVF fit (cluster/two_level.py, DESIGN.md §13): coarse
    spherical k-means over ``ClusterConfig.coarse_k`` cells, corpus
    partitioned by coarse assignment (lazy :class:`SubsetStore` views for
    DocStores), then per-cell fine fits — each sub-fit re-entering this
    registry with a flat sub-config, so both levels run on single_host /
    streaming unchanged.  Selected by ``ClusterConfig(coarse_k=...)``;
    emits a nested :class:`repro.cluster.model.TwoLevelFittedModel`.

The registry is open: registering a new runtime (e.g. multi-pod pipelined,
async parameter-server) is one class with a ``fit`` method — no new front
door.
"""
from __future__ import annotations

from typing import Protocol

import numpy as np

from repro import obs
from repro.cluster.config import ClusterConfig
from repro.core.lloyd import LloydResult, lloyd_fit, streaming_fit
from repro.core.meanindex import index_from_means_t
from repro.core.update import KMeansState
from repro.sparse.store import DocStore, as_store


class Strategy(Protocol):
    name: str

    def fit(self, docs, config: ClusterConfig, df=None) -> LloydResult: ...


class SingleHostStrategy:
    """The fused single-host Lloyd fit (DESIGN.md §8)."""

    name = "single_host"

    def fit(self, docs, config: ClusterConfig, df=None) -> LloydResult:
        return lloyd_fit(
            docs, k=config.k, algo=config.algo, backend=config.backend,
            params=config.params, batch_size=config.batch_size,
            max_iter=config.max_iter, est_grid=config.est_grid,
            est_iters=config.est_iters, seed=config.seed, df=df,
            tune=config.tune, tune_budget=config.tune_budget)


class StreamingStrategy:
    """The out-of-core chunk-scan fit over a DocStore (DESIGN.md §10).

    Resident SparseDocs are wrapped as an in-memory store
    (``config.chunk_size`` rows per chunk) — which is how
    ``algo_mode='minibatch'`` runs on ordinary corpora too.
    """

    name = "streaming"

    def fit(self, docs, config: ClusterConfig, df=None) -> LloydResult:
        store = as_store(docs, chunk_size=config.chunk_size)
        return streaming_fit(
            store, k=config.k, algo=config.algo, backend=config.backend,
            params=config.params, algo_mode=config.algo_mode,
            batch_size=config.batch_size, max_iter=config.max_iter,
            est_grid=config.est_grid, est_iters=config.est_iters,
            seed=config.seed, df=df,
            checkpoint_dir=config.checkpoint_dir,
            checkpoint_every=config.checkpoint_every,
            tune=config.tune, tune_budget=config.tune_budget)


class MeshStrategy:
    """The distributed loop behind the same estimator (DESIGN.md §4).

    The mesh state (sharded arrays, padded tails) stays an implementation
    detail: the strategy trims padding and repackages the final shard state
    as an ordinary :class:`KMeansState`, so everything downstream — the
    FittedModel artifact, predict/classify, save/load — is runtime-blind.
    That repackaging is the span ``repro.mesh.finish``; its counters go to
    the last history row, as ``lloyd_fit``'s finish does.
    """

    name = "mesh"

    def fit(self, docs, config: ClusterConfig, df=None) -> LloydResult:
        from repro.distributed.kmeans import mesh_fit

        if config.mesh is None:
            raise ValueError("MeshStrategy needs ClusterConfig(mesh=...)")
        with obs.fit() as rec:
            state, history, converged, params = mesh_fit(
                docs, config.k, config.mesh, algo=config.algo,
                backend=config.backend, max_iter=config.max_iter,
                obj_chunk=config.chunk_size, seed=config.seed,
                est_iters=config.est_iters, df=df,
                checkpoint_dir=config.checkpoint_dir,
                checkpoint_every=config.checkpoint_every,
                tune=config.tune)
            counts = obs.RowCounts(rec)
            n = docs.n_docs
            with obs.span("mesh.finish"):
                # The sharded state's means are consumed by the artifact's
                # index.
                index = index_from_means_t(state.means_t, params,
                                           moving=state.moving, donate=True)
                core_state = KMeansState(
                    index=index,
                    assign=state.assign[:n],
                    rho_self=state.rho_self[:n],
                    rho_self_prev=state.rho_prev[:n],
                    iteration=state.iteration,
                    ub=state.ub[:n],
                )
                assign = np.asarray(core_state.assign)
            if history:
                for key, value in counts.take().items():
                    history[-1][key] += value
        return LloydResult(
            state=core_state,
            assign=assign,
            history=history,
            params=params,
            converged=converged,
            n_iter=len(history),
        )


class TwoLevelStrategy:
    """The nested IVF fit (DESIGN.md §13) — coarse cells, then per-cell
    fine fits, every sub-fit re-entering this registry with a flat
    sub-config.  Returns a duck-typed result whose ``model`` attribute
    carries the ready-made nested artifact; the estimator adopts it
    instead of assembling a flat FittedModel."""

    name = "two_level"

    def fit(self, docs, config: ClusterConfig, df=None):
        from repro.cluster.two_level import two_level_fit

        if config.coarse_k is None:
            raise ValueError("TwoLevelStrategy needs ClusterConfig("
                             "coarse_k=...)")
        return two_level_fit(docs, config, df=df)


STRATEGIES: dict[str, Strategy] = {
    "single_host": SingleHostStrategy(),
    "streaming": StreamingStrategy(),
    "mesh": MeshStrategy(),
    "two_level": TwoLevelStrategy(),
}


def resolve_strategy(config: ClusterConfig, docs=None) -> Strategy:
    """(ClusterConfig, optional input corpus) -> execution strategy.

    The config picks the name (``mesh=`` → 'mesh', ``algo_mode='minibatch'``
    → 'streaming', else 'single_host'); an out-of-core :class:`DocStore`
    input promotes 'single_host' to 'streaming', since the fused resident
    fit cannot hold the corpus on device.
    """
    if isinstance(config, ClusterConfig):
        # Every front door fails fast on an unrunnable config (duck-typed
        # registry extensions validate — or not — on their own terms).
        config.validate()
    name = config.strategy
    if name == "single_host" and isinstance(docs, DocStore):
        name = "streaming"
    try:
        return STRATEGIES[name]
    except KeyError:
        raise ValueError(
            f"unknown execution strategy {name!r}; "
            f"valid strategies: {sorted(STRATEGIES)}") from None

"""The fitted-model artifact — one serializable noun for all three runtimes.

A :class:`FittedModel` is what a fit *produces* and what serving *consumes*:
the structured mean-inverted index (the SIVF stance: the index is a
first-class, reusable structure), the training labels and refreshed ρ_self,
the per-iteration diagnostic history, and enough metadata (algo, backend,
strategy) to reconstruct any runtime around it.  ``save``/``load`` ride the
fault-tolerant checkpoint store (checkpoint/store.py): the payload commits
atomically with a JSON metadata sidecar, so a crashed writer never leaves a
readable-but-half model on disk.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import (load_extra, restore_checkpoint,
                                    save_checkpoint)
from repro.cluster.classify import (classify_docs, classify_docs_routed,
                                    transform_docs)
from repro.core.meanindex import (MeanIndex, StructuralParams,
                                  build_mean_index, index_from_means_t)

MODEL_FORMAT = "repro.cluster/fitted-model-v1"
TWO_LEVEL_FORMAT = "repro.cluster/fitted-two-level-v1"


@dataclasses.dataclass
class FittedModel:
    """Fit output = index + labels + history + provenance.

    index:    MeanIndex — means, structural thresholds (t_th, v_th), ICP
              moving flags; everything assignment needs.
    labels:   (N,) int32 — final training assignment (empty for artifacts
              exported from a pure serving engine).
    rho_self: (N,) float32 — each doc's similarity to its own centroid, the
              next assignment step's pruning threshold ρ_max.
    history:  per-iteration diagnostics (mult, cpr, n_changed, objective, …).
    algo/backend/strategy: provenance — which algorithm, accumulator engine,
              and execution runtime produced the artifact.
    cursor:   streaming fits only — (next_epoch, next_chunk) where a
              resumed fit would continue; None for converged/resident
              fits.  A non-None cursor marks a usable-but-unconverged
              artifact (e.g. a max_iter-capped streaming fit).
    tuned:    the autotuned kernel-engine config the fit ran with, as the
              ``repro.tune.TunedConfig.to_dict()`` dict (None when tuning
              was off / missed).  ``load`` reseeds the process-wide
              ``TUNED_CACHE`` from it, so a later fit on the same corpus
              regime reuses the winner without re-searching.
    """

    index: MeanIndex
    labels: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int32))
    rho_self: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.float32))
    history: list = dataclasses.field(default_factory=list)
    converged: bool = True
    n_iter: int = 0
    algo: str = "esicp"
    backend: str = "auto"
    strategy: str = "single_host"
    cursor: tuple | None = None
    tuned: dict | None = None

    # -- derived -----------------------------------------------------------
    @property
    def k(self) -> int:
        return self.index.k

    @property
    def dim(self) -> int:
        return self.index.dim

    @property
    def params(self) -> StructuralParams:
        return self.index.params

    @property
    def objective(self) -> float:
        """J = Σ_i ρ_self(i) (Eq. 47) over the training corpus."""
        return float(np.sum(self.rho_self))

    # -- inference (the shared fused classify path) ------------------------
    def predict(self, docs, *, batch_size: int = 4096) -> np.ndarray:
        """(N,) int32 cluster ids — identical to
        ``ClusterEngine.from_model(self).classify(docs)[0]`` by construction
        (same path: cluster/classify.py)."""
        a, _ = classify_docs(self.index, docs, backend=self.backend,
                             batch_size=batch_size)
        return a

    def transform(self, docs, *, batch_size: int = 4096) -> np.ndarray:
        """(N, K) dense cosine similarities to every mean."""
        return transform_docs(self.index, docs, backend=self.backend,
                              batch_size=batch_size)

    def score(self, docs, *, batch_size: int = 4096) -> float:
        """Σ_i max_j cos(x_i, μ_j) — the spherical k-means objective of the
        best assignment (higher is better)."""
        _, sims = classify_docs(self.index, docs, backend=self.backend,
                                batch_size=batch_size)
        return float(np.sum(sims))

    def servable(self, **kw):
        """Wrap the artifact for the continuous-batching service plane —
        ``repro.serve.ServableClusterModel(self, **kw)`` (DESIGN.md §12).
        The servable inherits this model's backend and re-seeds the
        process-wide autotuner cache from ``tuned``, so the server runs the
        fit's kernel-engine winner without re-searching.  Load it (or the
        model directly) with ``ClusterServer.load``."""
        from repro.serve.servable import ServableClusterModel

        return ServableClusterModel(self, **kw)

    # -- persistence -------------------------------------------------------
    def save(self, directory: str, *, step: int = 0) -> str:
        """Atomically persist the artifact; returns the committed path."""
        tree = {
            "labels": np.asarray(self.labels, np.int32),
            "means_t": np.asarray(self.index.means_t, np.float32),
            "moving": np.asarray(self.index.moving, bool),
            "rho_self": np.asarray(self.rho_self, np.float32),
            "t_th": np.asarray(self.index.params.t_th, np.int32),
            "v_th": np.asarray(self.index.params.v_th, np.float32),
        }
        extra = {
            "format": MODEL_FORMAT,
            "algo": self.algo,
            "backend": self.backend,
            "strategy": self.strategy,
            "k": int(self.k),
            "dim": int(self.dim),
            "n_docs": int(np.shape(self.labels)[0]),
            "converged": bool(self.converged),
            "n_iter": int(self.n_iter),
            "history": self.history,
            "cursor": None if self.cursor is None else list(self.cursor),
            "tuned": self.tuned,
        }
        # keep=None: an artifact writer must never garbage-collect other
        # steps sharing the directory (e.g. a fit's training checkpoints).
        return save_checkpoint(directory, tree, step=step, keep=None,
                               extra=extra)

    @classmethod
    def load(cls, directory: str, *, step: int | None = None) -> FittedModel:
        extra = load_extra(directory, step=step)
        if (extra and extra.get("format") == TWO_LEVEL_FORMAT
                and cls is FittedModel):
            # Format dispatch: a flat loader pointed at a nested artifact
            # gets the nested model back (its flat surface is a superset).
            return TwoLevelFittedModel.load(directory, step=step)
        if not extra or extra.get("format") != MODEL_FORMAT:
            raise ValueError(
                f"{directory} holds no {MODEL_FORMAT} artifact "
                f"(found {extra.get('format') if extra else None!r})")
        n, d, k = extra["n_docs"], extra["dim"], extra["k"]
        example = {
            "labels": np.zeros((n,), np.int32),
            "means_t": np.zeros((d, k), np.float32),
            "moving": np.zeros((k,), bool),
            "rho_self": np.zeros((n,), np.float32),
            "t_th": np.asarray(0, np.int32),
            "v_th": np.asarray(0.0, np.float32),
        }
        tree, _ = restore_checkpoint(directory, example, step=step)
        tuned = extra.get("tuned")
        if tuned is not None and tuned.get("signature"):
            # Reseed the process cache: a fit on the same corpus regime in
            # this process reuses the artifact's winner without searching.
            from repro.tune import TUNED_CACHE, TunedConfig

            TUNED_CACHE.put(tuned["signature"], TunedConfig.from_dict(tuned))
        params = StructuralParams(t_th=jnp.asarray(tree["t_th"], jnp.int32),
                                  v_th=jnp.asarray(tree["v_th"], jnp.float32))
        index = index_from_means_t(jnp.asarray(tree["means_t"]), params,
                                   moving=jnp.asarray(tree["moving"]))
        return cls(index=index,
                   labels=np.asarray(tree["labels"], np.int32),
                   rho_self=np.asarray(tree["rho_self"], np.float32),
                   history=list(extra["history"]),
                   converged=extra["converged"],
                   n_iter=extra["n_iter"],
                   algo=extra["algo"],
                   backend=extra["backend"],
                   strategy=extra["strategy"],
                   cursor=(None if extra.get("cursor") is None
                           else tuple(extra["cursor"])),
                   tuned=tuned)


@dataclasses.dataclass
class TwoLevelFittedModel(FittedModel):
    """The nested two-level IVF artifact (DESIGN.md §13).

    Extends the flat artifact — ``index`` holds the CONCATENATED fine means
    (cell 0's clusters first, then cell 1's, …), so every flat surface
    (``transform``, flat ``classify_docs``, geometry, serving buckets)
    works unchanged and ``labels`` live in that global fine space — with
    the coarse level on top:

    coarse_index: MeanIndex over the K_c coarse cell means.
    cell_sizes:   (K_c,) int32 — fine clusters per cell; ``cell_starts``
                  (the offsets of each cell's block in ``index``) derive
                  as the exclusive cumsum.  Every cell holds >= 1 fine
                  centroid (empty coarse cells keep their coarse mean), so
                  a routed argmax always has a live candidate.
    n_probe:      default probe width for ``predict`` / serving (overridable
                  per call; n_probe = K_c is exactly the flat scan).
    cell_meta:    per-cell fit provenance ({n_docs, k, n_iter, converged}).
    """

    coarse_index: MeanIndex | None = None
    cell_sizes: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,), np.int32))
    n_probe: int = 1
    cell_meta: list = dataclasses.field(default_factory=list)

    # -- derived -----------------------------------------------------------
    @property
    def coarse_k(self) -> int:
        return self.coarse_index.k

    @property
    def cell_starts(self) -> np.ndarray:
        """(K_c,) int32 — offset of each cell's block in ``index``."""
        sizes = np.asarray(self.cell_sizes, np.int64)
        return np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int32)

    def _routed_operands(self):
        """Device operands of the routed classify, built once per model:
        (coarse_index, means_ext (D, K_eff+1) with the all-zero sentinel
        column, starts (K_c,), sizes (K_c,), cmax).  Cached so serving and
        repeated predicts re-trace nothing and re-upload nothing."""
        ops = self.__dict__.get("_routed_cache")
        if ops is None:
            sizes = jnp.asarray(np.asarray(self.cell_sizes), jnp.int32)
            starts = jnp.asarray(self.cell_starts, jnp.int32)
            means_ext = jnp.concatenate(
                [self.index.means_t,
                 jnp.zeros((self.dim, 1), jnp.float32)], axis=1)
            cmax = int(np.max(np.asarray(self.cell_sizes)))
            ops = (self.coarse_index, means_ext, starts, sizes, cmax)
            self.__dict__["_routed_cache"] = ops
        return ops

    # -- inference (coarse-routed) -----------------------------------------
    def predict(self, docs, *, batch_size: int = 4096,
                n_probe: int | None = None) -> np.ndarray:
        """(N,) global fine-cluster ids via the coarse-routed classify —
        scores K_c + Σ probed cell sizes centroids per object instead of
        K_eff (exact at n_probe = K_c; ANN below it)."""
        a, _ = classify_docs_routed(self, docs, n_probe=n_probe,
                                    batch_size=batch_size)
        return a

    def score(self, docs, *, batch_size: int = 4096,
              n_probe: int | None = None) -> float:
        _, sims = classify_docs_routed(self, docs, n_probe=n_probe,
                                       batch_size=batch_size)
        return float(np.sum(sims))

    # -- persistence -------------------------------------------------------
    def save(self, directory: str, *, step: int = 0) -> str:
        tree = {
            "labels": np.asarray(self.labels, np.int32),
            "means_t": np.asarray(self.index.means_t, np.float32),
            "moving": np.asarray(self.index.moving, bool),
            "rho_self": np.asarray(self.rho_self, np.float32),
            "t_th": np.asarray(self.index.params.t_th, np.int32),
            "v_th": np.asarray(self.index.params.v_th, np.float32),
            "coarse_means_t": np.asarray(self.coarse_index.means_t,
                                         np.float32),
            "coarse_t_th": np.asarray(self.coarse_index.params.t_th,
                                      np.int32),
            "coarse_v_th": np.asarray(self.coarse_index.params.v_th,
                                      np.float32),
            "cell_sizes": np.asarray(self.cell_sizes, np.int32),
        }
        extra = {
            "format": TWO_LEVEL_FORMAT,
            "algo": self.algo,
            "backend": self.backend,
            "strategy": self.strategy,
            "k": int(self.k),
            "dim": int(self.dim),
            "coarse_k": int(self.coarse_k),
            "n_probe": int(self.n_probe),
            "n_docs": int(np.shape(self.labels)[0]),
            "converged": bool(self.converged),
            "n_iter": int(self.n_iter),
            "history": self.history,
            "cell_meta": self.cell_meta,
            "cursor": None if self.cursor is None else list(self.cursor),
            "tuned": self.tuned,
        }
        return save_checkpoint(directory, tree, step=step, keep=None,
                               extra=extra)

    @classmethod
    def load(cls, directory: str, *,
             step: int | None = None) -> "TwoLevelFittedModel":
        extra = load_extra(directory, step=step)
        if not extra or extra.get("format") != TWO_LEVEL_FORMAT:
            raise ValueError(
                f"{directory} holds no {TWO_LEVEL_FORMAT} artifact "
                f"(found {extra.get('format') if extra else None!r})")
        n, d, k, k_c = (extra["n_docs"], extra["dim"], extra["k"],
                        extra["coarse_k"])
        example = {
            "labels": np.zeros((n,), np.int32),
            "means_t": np.zeros((d, k), np.float32),
            "moving": np.zeros((k,), bool),
            "rho_self": np.zeros((n,), np.float32),
            "t_th": np.asarray(0, np.int32),
            "v_th": np.asarray(0.0, np.float32),
            "coarse_means_t": np.zeros((d, k_c), np.float32),
            "coarse_t_th": np.asarray(0, np.int32),
            "coarse_v_th": np.asarray(0.0, np.float32),
            "cell_sizes": np.zeros((k_c,), np.int32),
        }
        tree, _ = restore_checkpoint(directory, example, step=step)
        tuned = extra.get("tuned")
        if tuned is not None and tuned.get("signature"):
            from repro.tune import TUNED_CACHE, TunedConfig

            TUNED_CACHE.put(tuned["signature"], TunedConfig.from_dict(tuned))
        params = StructuralParams(t_th=jnp.asarray(tree["t_th"], jnp.int32),
                                  v_th=jnp.asarray(tree["v_th"], jnp.float32))
        cparams = StructuralParams(
            t_th=jnp.asarray(tree["coarse_t_th"], jnp.int32),
            v_th=jnp.asarray(tree["coarse_v_th"], jnp.float32))
        return cls(
            index=index_from_means_t(jnp.asarray(tree["means_t"]), params,
                                     moving=jnp.asarray(tree["moving"])),
            coarse_index=build_mean_index(
                jnp.asarray(tree["coarse_means_t"]).T, cparams),
            cell_sizes=np.asarray(tree["cell_sizes"], np.int32),
            n_probe=int(extra["n_probe"]),
            cell_meta=list(extra.get("cell_meta") or []),
            labels=np.asarray(tree["labels"], np.int32),
            rho_self=np.asarray(tree["rho_self"], np.float32),
            history=list(extra["history"]),
            converged=extra["converged"],
            n_iter=extra["n_iter"],
            algo=extra["algo"],
            backend=extra["backend"],
            strategy=extra["strategy"],
            cursor=(None if extra.get("cursor") is None
                    else tuple(extra["cursor"])),
            tuned=tuned)


def load_model(directory: str, *, step: int | None = None) -> FittedModel:
    """Module-level alias for :meth:`FittedModel.load` (format-dispatching:
    a nested two-level artifact loads as :class:`TwoLevelFittedModel`)."""
    return FittedModel.load(directory, step=step)

"""``SphericalKMeans`` — a real sklearn-style estimator over the paper's fit.

Contract (Knittel et al., arXiv:2108.00895, make the case that a drop-in
estimator is what drives adoption of accelerated sparse spherical k-means):

  * ``fit`` returns ``self`` and populates trailing-underscore attributes:
    ``model_`` (the serializable FittedModel artifact), ``labels_``,
    ``history_``, ``state_``, ``params_``, ``n_iter_``, ``converged_``;
  * ``predict`` / ``transform`` / ``score`` share the fused classify path
    with ``serve.ClusterEngine`` (cluster/classify.py) — train and serve
    cannot disagree;
  * execution routes through pluggable strategies: ``mesh=`` dispatches the
    *same* estimator through the distributed loop (cluster/strategies.py).

Legacy surface (pre-redesign) stays importable behind deprecation shims:
``fit_result()`` returns the old LloydResult, and the old result attributes
(``.assign``, ``.history``, ``.state``, ``.objective``, ``.converged``,
``.n_iter``) forward from the estimator with a DeprecationWarning.  The one
exception is ``.params`` — it now always means the *constructor* threshold
spec; read the fitted thresholds from ``params_``.
"""
from __future__ import annotations

import warnings

import numpy as np

from repro import obs
from repro.cluster.config import ClusterConfig
from repro.cluster.model import FittedModel
from repro.cluster.strategies import resolve_strategy
from repro.core.backends import resolve_backend
from repro.core.estparams import EstGrid
from repro.core.lloyd import LloydResult

# Pre-redesign LloydResult fields readable straight off the fitted estimator.
_LEGACY_RESULT_ATTRS = {
    "assign": "labels_",
    "history": "history_",
    "state": "state_",
    "objective": "objective_",
    "converged": "converged_",
    "n_iter": "n_iter_",
}

# Attributes fit() populates — named in the not-fitted-yet error.
_FITTED_ATTRS = frozenset({
    "model_", "labels_", "history_", "state_", "params_", "n_iter_",
    "converged_", "objective_",
})


class SphericalKMeans:
    """sklearn-style front door over every runtime (see module docstring).

    algo: 'mivi' | 'icp' | 'es' | 'esicp' | 'ta-icp' | 'cs-icp'
    backend: 'reference' | 'pallas' | 'auto' — accumulator engine for the
            assignment AND update steps (core/backends.py; 'auto' = pallas
            on TPU).
    params: 'auto' (EstParams at iterations 1–2, the paper's default),
            StructuralParams for fixed thresholds, or None -> trivial.
    mesh:   optional jax Mesh — routes the fit through the distributed
            strategy; chunk_size is that runtime's per-shard object chunk.
    coarse_k / n_probe: the two-level IVF knobs (DESIGN.md §13) — a
            non-None coarse_k routes the fit through the 'two_level'
            strategy and ``model_`` becomes a nested TwoLevelFittedModel
            whose predict routes through the coarse level.
    """

    def __init__(self, k: int, *, algo: str = "esicp", params="auto",
                 backend: str = "reference", batch_size: int = 4096,
                 max_iter: int = 60, est_grid: EstGrid | None = None,
                 est_iters=(1, 2), seed: int = 0, mesh=None,
                 chunk_size: int = 1024, algo_mode: str = "full",
                 checkpoint_dir: str | None = None,
                 checkpoint_every: int = 5, tune: str = "off",
                 tune_budget=None, coarse_k: int | None = None,
                 n_probe: int = 1):
        self.k = k
        self.algo = algo
        self.backend = backend
        self.params = params
        self.batch_size = batch_size
        self.max_iter = max_iter
        self.est_grid = est_grid or EstGrid()
        self.est_iters = tuple(est_iters)
        self.seed = seed
        self.mesh = mesh
        self.chunk_size = chunk_size
        self.algo_mode = algo_mode
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every
        self.tune = tune
        self.tune_budget = tune_budget
        self.coarse_k = coarse_k
        self.n_probe = n_probe

    # -- config plumbing ---------------------------------------------------
    @property
    def config(self) -> ClusterConfig:
        """The declarative view of this estimator (rebuilt per access, so
        sklearn-style attribute mutation is honoured)."""
        return ClusterConfig(
            k=self.k, algo=self.algo, backend=self.backend,
            params=self.params, batch_size=self.batch_size,
            chunk_size=self.chunk_size, max_iter=self.max_iter,
            est_grid=self.est_grid, est_iters=self.est_iters,
            seed=self.seed, mesh=self.mesh, algo_mode=self.algo_mode,
            checkpoint_dir=self.checkpoint_dir,
            checkpoint_every=self.checkpoint_every, tune=self.tune,
            tune_budget=self.tune_budget, coarse_k=self.coarse_k,
            n_probe=self.n_probe)

    @classmethod
    def from_config(cls, config: ClusterConfig) -> SphericalKMeans:
        return cls(config.k, algo=config.algo, params=config.params,
                   backend=config.backend, batch_size=config.batch_size,
                   max_iter=config.max_iter, est_grid=config.est_grid,
                   est_iters=config.est_iters, seed=config.seed,
                   mesh=config.mesh, chunk_size=config.chunk_size,
                   algo_mode=config.algo_mode,
                   checkpoint_dir=config.checkpoint_dir,
                   checkpoint_every=config.checkpoint_every,
                   tune=config.tune, tune_budget=config.tune_budget,
                   coarse_k=config.coarse_k, n_probe=config.n_probe)

    # -- the estimator surface ---------------------------------------------
    def fit(self, docs, df=None) -> SphericalKMeans:
        """Cluster ``docs`` — a resident :class:`repro.sparse.SparseDocs`
        OR an out-of-core :class:`repro.sparse.DocStore` (which routes the
        fit through the streaming strategy); returns ``self`` (sklearn
        contract).  The whole call is the span ``repro.fit``, the parent
        of the fit's other spans (``repro.obs``)."""
        with obs.fit(), obs.span("fit"):
            cfg = self.config.validate()
            strategy = resolve_strategy(cfg, docs)
            result = strategy.fit(docs, cfg, df=df)
            self._fit_result = result
            tuned = getattr(result, "tuned", None)
            # Strategies that assemble their own artifact (two_level's
            # nested TwoLevelFittedModel) hand it over via
            # ``result.model``; everyone else gets the flat FittedModel
            # built here.
            model = getattr(result, "model", None)
            self.model_ = model if model is not None else FittedModel(
                index=result.state.index,
                labels=np.asarray(result.assign, np.int32),
                rho_self=np.asarray(result.state.rho_self, np.float32),
                history=list(result.history),
                converged=result.converged,
                n_iter=result.n_iter,
                algo=cfg.algo,
                backend=resolve_backend(cfg.backend).name,
                strategy=strategy.name,
                cursor=getattr(result, "cursor", None),
                tuned=None if tuned is None else tuned.to_dict(),
            )
        self.labels_ = self.model_.labels
        self.history_ = self.model_.history
        self.state_ = result.state
        self.params_ = result.params
        self.n_iter_ = result.n_iter
        self.converged_ = result.converged
        self.objective_ = result.objective   # J = Σ_i ρ_self(i) (Eq. 47)
        return self

    def fit_predict(self, docs, df=None) -> np.ndarray:
        return self.fit(docs, df=df).labels_

    def predict(self, docs) -> np.ndarray:
        """(N,) cluster ids vs the fitted index (shared classify path)."""
        return self._model().predict(docs, batch_size=self.batch_size)

    def transform(self, docs) -> np.ndarray:
        """(N, K) cosine similarities vs the fitted means."""
        return self._model().transform(docs, batch_size=self.batch_size)

    def score(self, docs) -> float:
        """Σ_i max_j cos(x_i, μ_j) (higher is better)."""
        return self._model().score(docs, batch_size=self.batch_size)

    # -- internals / legacy ------------------------------------------------
    def _model(self) -> FittedModel:
        if not hasattr(self, "model_"):
            raise AttributeError(
                "This SphericalKMeans instance is not fitted yet; "
                "call fit() first.")
        return self.model_

    def _result(self) -> LloydResult:
        if "_fit_result" not in self.__dict__:
            raise AttributeError(
                "This SphericalKMeans instance is not fitted yet; "
                "call fit() first.")
        return self._fit_result

    def fit_result(self) -> LloydResult:
        """Deprecated accessor for the pre-redesign ``fit`` return value."""
        warnings.warn(
            "SphericalKMeans.fit() now returns the estimator; read model_/"
            "labels_/history_/state_, or fit_result() during migration.",
            DeprecationWarning, stacklevel=2)
        return self._result()

    def __getattr__(self, name):
        new = _LEGACY_RESULT_ATTRS.get(name)
        if new is not None and "_fit_result" in self.__dict__:
            warnings.warn(
                f"SphericalKMeans.{name} is deprecated (fit() returns the "
                f"estimator since the repro.cluster redesign); use {new}.",
                DeprecationWarning, stacklevel=2)
            return getattr(self._fit_result, name)
        if name in _FITTED_ATTRS or new is not None:
            raise AttributeError(
                f"SphericalKMeans.{name} is only available after fit(); "
                "this instance is not fitted yet.")
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}")

"""Whole fits back to back on a corpus resident on the device.

Traffic keys: ``max_iter``.  The program is reached only through
``repro.cluster.fit`` with a ``ClusterConfig`` that sets k, max_iter and
seed and nothing else.  The window always runs one fit, and starts another
only if, by the mean of the fits so far, it should end within the window's
seconds.  ``fit_s_per_iter`` is the wall time of the fits over the Lloyd
iterations they ran.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import check, reference
from chipbench.corpus import make_corpus, spec_of
from chipbench.drivers import sub_seeds


def program_docs(corpus):
    from repro.sparse import SparseDocs

    return SparseDocs(ids=corpus.ids, vals=corpus.vals, nnz=corpus.nnz,
                      dim=corpus.dim, _df=corpus.df_sorted)


def fit_config(k: int, max_iter: int, seed: int):
    from repro.cluster import ClusterConfig

    return ClusterConfig(k=k, max_iter=max_iter, seed=seed)


class Driver:

    def __init__(self, config: dict, traffic: dict, seed: int, span, log):
        self.config, self.traffic, self.span, self.log = (config, traffic,
                                                          span, log)
        self.seeds = sub_seeds(seed)
        self.k = int(config["k"])
        self.max_iter = int(traffic["max_iter"])
        self.fits: list = []
        self.means_t = None

    def setup(self, seconds: float) -> None:
        from repro.cluster import fit

        t0 = time.perf_counter()
        self.corpus = make_corpus(spec_of(self.config), self.seeds["corpus"])
        self.docs = program_docs(self.corpus)
        jax.block_until_ready(self.docs.vals)
        t1 = time.perf_counter()
        self.cfg = fit_config(self.k, self.max_iter, self.seeds["fit"])
        # The fused remainder of a fit is compiled for its static number of
        # steps, so only a whole fit warms every program the window runs.
        warm = fit(self.docs, self.cfg)
        jax.block_until_ready(warm.index.means_t)
        del warm
        self.log(f"setup corpus_s={t1 - t0:.3f} warm_fit_s="
                 f"{time.perf_counter() - t1:.3f} "
                 f"pad_width={self.corpus.pad_width}")

    def fit_once(self):
        """One whole fit through the program's entry point, timed to its
        end on the device; returns the fitted model."""
        from repro.cluster import fit

        with self.span("fit"):
            ts = time.perf_counter()
            model = fit(self.docs, self.cfg)
            jax.block_until_ready(model.index.means_t)
            te = time.perf_counter()
        self.fits.append({
            "seconds": te - ts, "n_iter": int(model.n_iter),
            "history": list(model.history),
            "labels": np.asarray(model.labels),
            "objectives": [float(h["objective"]) for h in model.history]})
        return model

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        while True:
            model = self.fit_once()
            elapsed = time.perf_counter() - t0
            if elapsed + elapsed / len(self.fits) > seconds:
                break
            del model
        self.means_t = model.index.means_t

    def end_to_end(self) -> dict:
        iters = sum(f["n_iter"] for f in self.fits)
        return {"fit_s_per_iter": sum(f["seconds"] for f in self.fits)
                / max(iters, 1)}

    def counts(self) -> tuple:
        return len(self.fits), 0

    def release(self) -> None:
        self.docs = None

    def numbers(self) -> dict:
        out = check.fit_numbers(
            self.corpus, k=self.k, seed=self.seeds["fit"],
            max_iter=self.max_iter,
            fits=[(f["labels"], f["objectives"]) for f in self.fits],
            means_t=self.means_t)
        self.means_t = None
        return out

    def record(self) -> dict:
        c = self.corpus
        return {"fits": [{"seconds": f["seconds"], "n_iter": f["n_iter"],
                          "history": f["history"]} for f in self.fits],
                "corpus": {"n_docs": c.n_docs, "pad_width": c.pad_width,
                           "dim": c.dim,
                           "nnz_total": int(np.sum(np.asarray(c.nnz)))},
                "k": self.k}

    def close(self) -> None:
        pass


def control(config: dict, traffic: dict, seed: int) -> dict:
    """The fit check's numbers with the plain reference in the program's
    place, its means and document values kept in bfloat16: one precision
    below the configuration's float32.  The check is sound only if these
    numbers fail the cell's limits."""
    seeds = sub_seeds(seed)
    corpus = make_corpus(spec_of(config), seeds["corpus"])
    k, max_iter = int(config["k"]), int(traffic["max_iter"])
    low = reference.lloyd(corpus.ids, corpus.vals, corpus.nnz, k=k,
                          dim=corpus.dim, seed=seeds["fit"], max_iter=max_iter,
                          store=jnp.bfloat16)
    return check.fit_numbers(corpus, k=k, seed=seeds["fit"],
                             max_iter=max_iter,
                             fits=[(low.labels, low.objectives)],
                             means_t=low.means_t)

"""Whole K-sharded fits back to back on a corpus resident on the devices.

Traffic keys: ``max_iter``, ``mesh`` ({"data": d, "model": m}) and
``warmup_max_iter``.  The program is reached only through the ``fit``
driver, so only through ``repro.cluster.fit``, with its ``ClusterConfig``
given one more field: ``mesh``, a ``jax.sharding.Mesh`` of the first d*m
devices shaped (data, model), so each device holds K/m means.  The window's
rule and ``fit_s_per_iter`` are the ``fit`` driver's.  The warm-up fit in
set-up runs ``warmup_max_iter`` iterations: every iteration of a mesh fit
calls the same step executable, so the EstParams iterations and one steady
step reach every program the window runs.

The check compares with ``chipbench.reference_kblocks``, the plain reference
with its means in m K-blocks, block b on the device that holds the
program's block b.
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference_kblocks
from chipbench.corpus import make_corpus, spec_of
from chipbench.drivers import fit, sub_seeds


def mesh_of(shape: dict):
    from jax.sharding import Mesh

    d, m = int(shape["data"]), int(shape["model"])
    return Mesh(np.asarray(jax.devices()[:d * m]).reshape(d, m),
                ("data", "model"))


def fit_numbers(corpus, *, k: int, seed: int, max_iter: int, devices,
                fits: list, blocks: list, ref=None) -> dict:
    """``check.fit_numbers`` with the means in K-blocks on ``devices``:
    ``blocks`` are the (D, K/len(devices)) means the last fit returned,
    block b on device b; the list is emptied once the update check has
    read it, so that the reference fit, computed here when ``ref`` is not
    given, has the memory."""
    labels = np.asarray(fits[-1][0])
    f32 = reference_kblocks.Blocks(corpus.ids, corpus.vals, corpus.nnz, k=k,
                                   devices=devices)
    update_gap = f32.gap(labels, blocks)
    del f32, blocks[:]
    if ref is None:
        ref = reference_kblocks.lloyd(corpus.ids, corpus.vals, corpus.nnz,
                                      k=k, dim=corpus.dim, seed=seed,
                                      max_iter=max_iter, devices=devices)
    label_share, objective_gap = 0.0, 0.0
    for got, objectives in fits:
        label_share = max(label_share,
                          float(np.mean(np.asarray(got) != ref.labels)))
        if len(objectives) != len(ref.objectives):
            objective_gap = math.inf
            continue
        for j_got, j_ref in zip(objectives, ref.objectives):
            objective_gap = max(objective_gap,
                                abs(j_got - j_ref) / abs(j_ref))
    return {"label_share": label_share, "update_gap": update_gap,
            "objective_gap": objective_gap}


def shard_blocks(means_t, devices) -> list:
    """The K-blocks of a (D, K) array sharded along K, block b the shard on
    ``devices[b]``."""
    on = {s.device: s for s in means_t.addressable_shards}
    k_b = means_t.shape[1] // len(devices)
    blocks = []
    for b, d in enumerate(devices):
        if (on[d].index[1].start or 0) != b * k_b:
            raise ValueError(f"the shard on {d} does not hold block {b}")
        blocks.append(on[d].data)
    return blocks


class Driver(fit.Driver):

    def __init__(self, config: dict, traffic: dict, seed: int, span, log):
        super().__init__(config, traffic, seed, span, log)
        self.mesh = mesh_of(traffic["mesh"])
        self.warmup_max_iter = int(traffic["warmup_max_iter"])

    def block_devices(self) -> list:
        return list(self.mesh.devices[0])

    def config_of(self, max_iter: int):
        return dataclasses.replace(
            fit.fit_config(self.k, max_iter, self.seeds["fit"]),
            mesh=self.mesh)

    def setup(self, seconds: float) -> None:
        t0 = time.perf_counter()
        self.corpus = make_corpus(spec_of(self.config), self.seeds["corpus"])
        self.docs = fit.program_docs(self.corpus)
        jax.block_until_ready(self.docs.vals)
        t1 = time.perf_counter()
        self.cfg = self.config_of(self.warmup_max_iter)
        self.fit_once()                 # the model is dropped at once
        self.fits.clear()
        self.cfg = self.config_of(self.max_iter)
        self.log(f"setup corpus_s={t1 - t0:.3f} warm_fit_s="
                 f"{time.perf_counter() - t1:.3f} "
                 f"pad_width={self.corpus.pad_width} "
                 f"mesh={dict(self.mesh.shape)}")

    def numbers(self) -> dict:
        devices = self.block_devices()
        blocks = shard_blocks(self.means_t, devices)
        self.means_t = None
        return fit_numbers(
            self.corpus, k=self.k, seed=self.seeds["fit"],
            max_iter=self.max_iter, devices=devices,
            fits=[(f["labels"], f["objectives"]) for f in self.fits],
            blocks=blocks)

    def record(self) -> dict:
        return {**super().record(), "mesh": dict(self.mesh.shape)}


def control(config: dict, traffic: dict, seed: int) -> dict:
    """The check's numbers with the K-blocked reference in the program's
    place, its means and document values kept in bfloat16: one precision
    below the configuration's float32."""
    seeds = sub_seeds(seed)
    corpus = make_corpus(spec_of(config), seeds["corpus"])
    k, max_iter = int(config["k"]), int(traffic["max_iter"])
    devices = list(mesh_of(traffic["mesh"]).devices[0])
    low = reference_kblocks.lloyd(corpus.ids, corpus.vals, corpus.nnz, k=k,
                                  dim=corpus.dim, seed=seeds["fit"],
                                  max_iter=max_iter, devices=devices,
                                  store=jnp.bfloat16)
    return fit_numbers(corpus, k=k, seed=seeds["fit"], max_iter=max_iter,
                       devices=devices, fits=[(low.labels, low.objectives)],
                       blocks=low.blocks)

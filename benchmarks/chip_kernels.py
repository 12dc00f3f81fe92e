"""Time the three Pallas kernels of one esicp iteration at a published width.

    python -m benchmarks.chip_kernels [--width pubmed8m|nyt1m] [--k K]
                                      [--rows N] [--reps R] [--seed S]

On ``rows`` synthetic documents at the width's D and nt_mean: the ES
assignment gather (``esicp_gather`` with the full similarity), the cluster
sums (``segment_update``) and the ρ_self refresh (``rho_gather``).  Each
kernel is compiled once (its compile seconds reported), then run ``reps``
times; the median host-clock seconds per call and the seconds per occupied
(row tile, D-block, K-superblock) cell are printed as one JSON line per
kernel.  Run it on the chip; on CPU the kernels run in the interpreter and
the times mean nothing.
"""
from __future__ import annotations

import argparse
import importlib
import json
import statistics
import time

import jax
import jax.numpy as jnp

WIDTHS = {"pubmed8m": 128, "nyt1m": None}     # P: pubmed pads to 128 slots


def _time(fn, args, reps: int):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    runs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        runs.append(time.perf_counter() - t0)
    return first, statistics.median(runs)


def main(argv=None) -> int:
    from repro.data import CorpusSpec, make_corpus
    from repro.kernels import ops
    from repro.kernels.plan import DEFAULT_B_BLK, DEFAULT_D_BLK, occupancy_map
    from repro.tune.config import DEFAULT_K_BLK

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--width", choices=sorted(WIDTHS), default="pubmed8m")
    ap.add_argument("--k", type=int, default=4096)
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    job = importlib.import_module(f"repro.configs.{args.width}").config()
    docs, _, _, _ = make_corpus(CorpusSpec(
        n_docs=args.rows, vocab=job.vocab, nt_mean=job.nt_mean,
        pad_to=WIDTHS[args.width], seed=args.seed))
    d, k = job.vocab, args.k
    means = jax.random.uniform(jax.random.PRNGKey(args.seed), (d, k),
                               jnp.float32) / jnp.sqrt(d)
    assign = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                (args.rows,), 0, k)
    occ = occupancy_map(docs.ids, docs.vals, dim=d)
    kp = k + (-k) % DEFAULT_K_BLK
    cells = int(jnp.sum(occ != 0)) * (kp // ops._pick_k_sup(kp, DEFAULT_K_BLK,
                                                            None))
    base = {"device": jax.devices()[0].device_kind, "width": args.width,
            "rows": args.rows, "pad_width": docs.pad_width, "dim": d, "k": k,
            "b_blk": DEFAULT_B_BLK, "d_blk": DEFAULT_D_BLK,
            "occupied_cells": cells}
    kernels = {
        "esicp_gather": (jax.jit(lambda i, v, m: ops.esicp_gather(
            i, v, m, d // 2, 0.01, with_sims=True)),
            (docs.ids, docs.vals, means)),
        "segment_update": (jax.jit(lambda a, i, v: ops.segment_update(
            a, i, v, k=k, d=d)), (assign, docs.ids, docs.vals)),
        "rho_gather": (jax.jit(ops.rho_gather),
                       (assign, docs.ids, docs.vals, means)),
    }
    for name, (fn, fargs) in kernels.items():
        first, med = _time(fn, fargs, args.reps)
        print(json.dumps({**base, "kernel": name,
                          "first_call_s": round(first, 4),
                          "median_s": round(med, 4),
                          "us_per_cell": round(1e6 * med / max(cells, 1),
                                               3)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

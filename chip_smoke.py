"""Smoke test of the clustering system's main path on one TPU chip.

    python chip_smoke.py [--seed S] [--n-docs N]
    python chip_smoke.py [--seed S] --four-chips

One process, phases in order, each printing its wall time and compile time
on a line of its own:

1. device  - JAX must report a TPU; anything else exits non-zero, naming
             the platform found.  There is no CPU fallback.
2. corpus  - a synthetic corpus from ``--seed`` at pubmed8m's published
             width (D=141,043, 58.96 distinct terms per document, P=128).
3. fit     - ``repro.cluster.fit`` with ``backend="auto"`` (the Pallas
             kernels on TPU) and again with ``backend="reference"`` from the
             same seed; their labels are compared (``compare_fits``).
4. serve   - ``save`` -> ``FittedModel.load`` -> ``ClusterServer``; classify
             requests of mixed sizes must equal ``predict`` on the same rows.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
A failed phase raises, so the script exits non-zero and prints no result.

``--four-chips`` runs only the mesh path and what it is compared with:
``SphericalKMeans(k, mesh=...)`` on a ("data", "model") mesh over four chips
against the one-device fit, then a mesh fit at nyt1m's published width.

The persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when set,
else ``<checkout>/.jax_cache`` (``repro.compile_cache``).
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

PAD_WIDTH = 128        # tuple slots per document (P)
# One chip: K=80,000 (pubmed8m's) is cut to 4096.  Compiled for a
# described v5e, the fused esicp fit program needs 18.4 GB of HBM at
# K=8192 and 9.5 GB at 4096 for 131,072 documents, 20.7 and 11.4 GB for
# 10^6 (arguments + temporaries, tests/test_tpu_compile.py).
K_ONE_CHIP = 4096
MAX_ITER = 4

# Near-tie tolerance of the backend comparison (DESIGN.md section 5).
TIE_GAP = 1e-5
OBJECTIVE_RTOL = 1e-5

# Four-chip run: the parity size one chip holds, and nyt1m's width.
MESH_PARITY_DOCS, MESH_PARITY_K = 16_384, 1024
MESH_NYT_DOCS, MESH_NYT_ITERS = 65_536, 3

# Request sizes of the serving phase: one row, a partial bucket, a full
# bucket, and a request larger than the biggest bucket (split in two).
REQUEST_SIZES = (1, 5, 40, 256, 300)


def log(msg: str) -> None:
    print(msg, flush=True)


class _CompileClock:
    """Sums XLA compile time (persistent-cache reads included) and counts
    persistent-cache hits, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


_CLOCK: _CompileClock | None = None


@contextlib.contextmanager
def phase(name: str):
    """Time a phase: prints ``phase=<name> wall_s=... compile_s=...`` and
    the process's peak device bytes so far (the largest over devices), so
    the phase that raised the peak can be read off."""
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = _CompileClock()
    c0, h0 = _CLOCK.seconds, _CLOCK.hits
    t0 = time.perf_counter()
    yield
    peaks = [p for p in peak_memory() if p is not None]
    log(f"phase={name} wall_s={time.perf_counter() - t0:.3f} "
        f"compile_s={_CLOCK.seconds - c0:.3f} "
        f"cache_hits={_CLOCK.hits - h0} "
        f"peak_bytes={max(peaks) if peaks else None}")


def device_check(n_chips: int = 1) -> dict:
    """The device JAX reports; raises unless it is a TPU with enough chips."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found platform {d0.platform!r} "
                           f"({d0.device_kind}); this smoke runs on a TPU "
                           f"only")
    if len(devs) < n_chips:
        raise RuntimeError(f"{n_chips} chips needed, JAX found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def make_docs(job, n_docs: int, seed: int, *,
              pad_to: int | None = PAD_WIDTH):
    """A synthetic corpus at a job's published width (D, nt_mean) with
    ``n_docs`` documents (repro.data.CorpusSpec)."""
    import jax
    from repro.data import CorpusSpec, make_corpus

    docs, _, _, _ = make_corpus(CorpusSpec(
        n_docs=n_docs, vocab=job.vocab, nt_mean=job.nt_mean, pad_to=pad_to,
        seed=seed))
    jax.block_until_ready(docs.vals)
    return docs


def fit_model(docs, *, k: int, backend: str, max_iter: int, seed: int):
    from repro.cluster import ClusterConfig, fit

    model = fit(docs, ClusterConfig(k=k, algo="esicp", backend=backend,
                                    max_iter=max_iter, seed=seed))
    check_model(model, docs.n_docs, k)
    return model


def _iter_seconds(model) -> list:
    """Host-clock seconds per Lloyd iteration from the fit history (the
    fused remainder reports its mean per iteration)."""
    return [round(h["elapsed_s"], 3) for h in model.history]


def check_model(model, n: int, k: int) -> None:
    import jax.numpy as jnp
    import numpy as np

    labels = np.asarray(model.labels)
    if labels.shape != (n,) or labels.min() < 0 or labels.max() >= k:
        raise AssertionError(f"labels: shape {labels.shape}, range "
                             f"[{labels.min()}, {labels.max()}] for K={k}")
    if not np.isfinite(model.objective) or model.objective <= 0:
        raise AssertionError(f"objective J={model.objective}")
    if not bool(jnp.all(jnp.isfinite(model.index.means_t))):
        raise AssertionError("non-finite means")


def top2_gap(index, docs, rows) -> "np.ndarray":
    """Reference-backend top-1 minus top-2 similarity of ``rows``."""
    import numpy as np
    from repro.cluster import transform_docs
    from repro.sparse import SparseDocs

    if len(rows) == 0:
        return np.zeros((0,), np.float32)
    sub = SparseDocs(ids=docs.ids[rows], vals=docs.vals[rows],
                     nnz=docs.nnz[rows], dim=docs.dim)
    sims = np.sort(transform_docs(index, sub, backend="reference"), axis=1)
    return sims[:, -1] - sims[:, -2]


def compare_fits(docs, fast, ref) -> dict:
    """Labels of the two fits must be equal, except at near-ties.

    A differing row passes only where the reference fit's own top-2 gap is
    below TIE_GAP, and the objectives must agree to OBJECTIVE_RTOL; any
    other difference raises (DESIGN.md section 5)."""
    import numpy as np

    la, lr = np.asarray(fast.labels), np.asarray(ref.labels)
    rows = np.nonzero(la != lr)[0]
    gaps = top2_gap(ref.index, docs, rows)
    j_fast, j_ref = float(fast.objective), float(ref.objective)
    rel = abs(j_fast - j_ref) / abs(j_ref)
    out = {"n_docs": int(la.size), "n_diff": int(rows.size),
           "max_gap": float(gaps.max(initial=0.0)),
           "j_fast": j_fast, "j_ref": j_ref, "j_rel_diff": rel}
    log("compare " + json.dumps(out))
    if rows.size and out["max_gap"] >= TIE_GAP:
        raise AssertionError(
            f"{rows.size} labels differ and not only at near-ties: max "
            f"reference top-2 gap {out['max_gap']:.3g} >= {TIE_GAP}")
    if rel > OBJECTIVE_RTOL:
        raise AssertionError(f"objective differs: {j_fast} vs {j_ref} "
                             f"(rel {rel:.3g} > {OBJECTIVE_RTOL})")
    return out


def serve_check(model, docs, seed: int) -> dict:
    """save -> load -> ClusterServer; served labels must equal predict."""
    import numpy as np
    from repro.cluster import FittedModel
    from repro.serve import ClusterServer

    rng = np.random.default_rng(seed)
    ids, vals, nnz = (np.asarray(a) for a in (docs.ids, docs.vals,
                                               docs.nnz))
    with tempfile.TemporaryDirectory() as tmp:
        model.save(tmp)
        served = FittedModel.load(tmp)
    if not np.array_equal(np.asarray(served.index.means_t),
                          np.asarray(model.index.means_t)):
        raise AssertionError("loaded means differ from the saved model")
    requests = [rng.choice(docs.n_docs, size=s, replace=False)
                for s in REQUEST_SIZES]
    with ClusterServer() as server:
        server.load("smoke", served)
        futures = [server.submit("smoke", (ids[r], vals[r], nnz[r]))
                   for r in requests]
        answers = [f.result(timeout=600) for f in futures]
    rows = np.concatenate(requests)
    from repro.sparse import SparseDocs
    expect = served.predict(SparseDocs(ids=docs.ids[rows],
                                       vals=docs.vals[rows],
                                       nnz=docs.nnz[rows], dim=docs.dim))
    got = np.concatenate([a for a, _ in answers])
    if not np.array_equal(got, expect):
        raise AssertionError(f"served labels differ from predict on "
                             f"{int(np.sum(got != expect))} of {got.size}")
    out = {"requests": list(REQUEST_SIZES), "rows": int(got.size),
           "backend": served.backend, "mismatches": 0}
    log("serve " + json.dumps(out))
    return out


def peak_memory() -> list:
    """Peak bytes in use on each device, as the runtime reports it."""
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def run_one_chip(args) -> dict:
    from repro.configs import pubmed8m

    with phase("device"):
        device = device_check()
    log("device " + json.dumps(device))
    job = pubmed8m.config()
    k = K_ONE_CHIP
    log("reduced " + json.dumps({
        "n_docs": f"{job.n_docs} -> {args.n_docs} (run time: the Pallas "
                  f"fit's iteration time grows with N, and 10^6 documents "
                  f"would not finish 4 iterations within 20 minutes)",
        "k": f"{job.k} -> {k} (the fused fit program must fit one "
             f"chip's 16 GB)"}))
    log(f"sizes n_docs={args.n_docs} dim={job.vocab} nt_mean={job.nt_mean} "
        f"pad_width={PAD_WIDTH} k={k} max_iter={MAX_ITER} seed={args.seed}")
    with phase("corpus"):
        docs = make_docs(job, args.n_docs, args.seed)
    with phase("fit_auto"):
        fast = fit_model(docs, k=k, backend="auto", max_iter=MAX_ITER,
                         seed=args.seed)
    if fast.backend != "pallas":
        raise AssertionError(f"backend='auto' ran {fast.backend!r} on TPU")
    log(f"fit_auto backend={fast.backend} n_iter={fast.n_iter} "
        f"J={fast.objective} iter_s={_iter_seconds(fast)}")
    with phase("fit_reference"):
        ref = fit_model(docs, k=k, backend="reference", max_iter=MAX_ITER,
                        seed=args.seed)
    log(f"fit_reference n_iter={ref.n_iter} J={ref.objective} "
        f"iter_s={_iter_seconds(ref)}")
    with phase("compare"):
        compare_fits(docs, fast, ref)
    del ref
    gc.collect()
    with phase("serve"):
        serve_check(fast, docs, args.seed)
    log(f"peak_bytes_in_use={peak_memory()}")
    return device


def run_four_chips(args) -> dict:
    """The mesh path and what it is compared with, on one 4-chip host."""
    from repro.cluster import SphericalKMeans
    from repro.configs import nyt1m, pubmed8m
    from repro.launch.mesh import make_test_mesh

    with phase("device"):
        device = device_check(4)
    log("device " + json.dumps(device))
    mesh = make_test_mesh((1, 4), ("data", "model"))
    log(f"mesh {dict(mesh.shape)}")

    # 1. At a size one chip holds: the mesh fit (K sharded over 'model')
    #    against the one-device fit of the same corpus and seed.
    job = pubmed8m.config()
    k, n = MESH_PARITY_K, MESH_PARITY_DOCS
    log(f"sizes parity n_docs={n} dim={job.vocab} k={k} "
        f"max_iter={MAX_ITER} backend=auto")
    with phase("corpus_parity"):
        docs = make_docs(job, n, args.seed)
    with phase("fit_mesh_parity"):
        on_mesh = SphericalKMeans(k, backend="auto", mesh=mesh,
                                  max_iter=MAX_ITER,
                                  seed=args.seed).fit(docs).model_
        check_model(on_mesh, n, k)
    with phase("fit_one_device_parity"):
        one = fit_model(docs, k=k, backend="auto", max_iter=MAX_ITER,
                        seed=args.seed)
    with phase("compare_parity"):
        compare_fits(docs, on_mesh, one)
    del docs, on_mesh, one
    gc.collect()

    # 2. nyt1m's published width: the (D, K) index is 19.8 GB, 4.95 GB per
    #    chip.  The reference engine runs it: compiled for a 2x2 v5e, the
    #    pallas step needs 20.2 GB per chip (its kernel wrappers pad the
    #    index and the cluster sums), the reference step 15.1 GB
    #    (tests/test_tpu_compile.py, DESIGN.md section 5).
    nyt = nyt1m.config()
    n = MESH_NYT_DOCS
    log("reduced " + json.dumps({
        "n_docs": f"{nyt.n_docs} -> {n} (per-chip HBM: index, cluster "
                  f"sums and new means take 15 of 16 GB)",
        "backend": "auto -> reference (the pallas mesh step needs 20.2 GB "
                   "per chip at this width)"}))
    log(f"sizes nyt n_docs={n} dim={nyt.vocab} nt_mean={nyt.nt_mean} "
        f"k={nyt.k} max_iter={MESH_NYT_ITERS} backend=reference")
    with phase("corpus_nyt"):
        docs = make_docs(nyt, n, args.seed, pad_to=None)
    log(f"pad_width={docs.pad_width}")
    with phase("fit_mesh_nyt"):
        model = SphericalKMeans(nyt.k, backend="reference", mesh=mesh,
                                max_iter=MESH_NYT_ITERS,
                                seed=args.seed).fit(docs).model_
        check_model(model, n, nyt.k)
    log(f"fit_mesh_nyt n_iter={model.n_iter} J={model.objective} "
        f"n_changed={[h['n_changed'] for h in model.history]} "
        f"means_sharding={model.index.means_t.sharding.spec}")
    log(f"peak_bytes_in_use={peak_memory()}")
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-docs", type=int, default=131_072,
                    help="corpus size of the one-chip run")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh path")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    log(f"compile_cache={enable_compile_cache()}")
    device = run_four_chips(args) if args.four_chips else run_one_chip(args)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

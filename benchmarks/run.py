"""Benchmark harness — one module per paper table/figure (DESIGN.md §7).

Prints ``name,us_per_call,derived`` CSV rows.  Run everything:

    PYTHONPATH=src python -m benchmarks.run
    PYTHONPATH=src python -m benchmarks.run --only table4,fig7

The ``fused`` and ``kernels`` suites additionally write
``BENCH_fused_iteration.json`` / ``BENCH_kernels.json`` so the update-phase
and per-kernel perf trajectories are machine-readable across PRs; their
rows carry ``platform``/``interpret`` execution metadata (and the kernel
suite per-kernel ``speedup`` ratios) so interpret-mode Pallas timings are
flagged as such.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

SUITES = [
    ("fig2", "benchmarks.fig2_ucs"),
    ("fig4", "benchmarks.fig4_cps"),
    ("table2", "benchmarks.table2_loop_order"),
    ("table4", "benchmarks.table4_compare"),
    ("table6", "benchmarks.table6_nyt"),
    ("fig7", "benchmarks.fig7_iterations"),
    ("fig10", "benchmarks.fig10_threshold"),
    ("fig13", "benchmarks.fig13_estparams"),
    ("ablation", "benchmarks.ablation_thresholds"),
    ("apph", "benchmarks.apph_seeding"),
    ("roofline", "benchmarks.roofline_report"),
    ("fused", "benchmarks.fused_iteration"),
    ("kernels", "benchmarks.kernel_suite"),
    ("pruning", "benchmarks.pruning_suite"),
    ("serving", "benchmarks.serving_suite"),
    ("ivf", "benchmarks.ivf_suite"),
]

JSON_SUITES = {"fused": "BENCH_fused_iteration.json",
               "kernels": "BENCH_kernels.json",
               "pruning": "BENCH_pruning.json",
               "serving": "BENCH_serving.json",
               "ivf": "BENCH_ivf.json"}


def _as_csv(row) -> str:
    """Printable CSV line for a row — dict rows render their core columns
    (full metadata lives in the JSON artifact).  ``us_per_call`` is
    optional: rows that cannot honestly report a wall time omit it."""
    if isinstance(row, str):
        return row
    us = row.get("us_per_call")
    line = (f"{row['name']},{'' if us is None else f'{us:.2f}'},"
            f"{row.get('backend', '')}")
    if "warmup_us" in row:
        line += f",{row['warmup_us']:.2f}"
    return line


def write_bench_json(rows, path: str) -> str:
    """Bench rows -> JSON file.

    Rows are either dicts (``benchmarks.common.bench_row`` — carry the
    execution metadata ``platform``/``interpret`` and any suite extras such
    as per-kernel ``speedup``) or legacy ``name,us_per_call,derived
    [,warmup_us]`` CSV strings.  The derived column of CSV rows carries the
    backend name; the optional 4th column is the per-case warmup
    (compile/trace) time, recorded as a ``warmup_us`` field so steady-state
    ``us_per_call`` is never conflated with one-off compilation again.
    """
    entries = []
    for row in rows:
        if isinstance(row, dict):
            entries.append(dict(row))
            continue
        name, us, rest = row.split(",", 2)
        derived, _, warmup = rest.partition(",")
        entry = {"name": name, "us_per_call": float(us), "backend": derived}
        if warmup:
            entry["warmup_us"] = float(warmup)
        entries.append(entry)
    with open(path, "w") as f:
        json.dump(entries, f, indent=2)
        f.write("\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite prefixes")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for name, module in SUITES:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["run"])
            rows = mod.run()
            for row in rows:
                print(_as_csv(row), flush=True)
            if name in JSON_SUITES:
                write_bench_json(rows, JSON_SUITES[name])
            print(f"{name}/_suite,{(time.time() - t0) * 1e6:.0f},elapsed",
                  flush=True)
        except Exception as e:
            failures += 1
            print(f"{name}/_suite_FAILED,0,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()

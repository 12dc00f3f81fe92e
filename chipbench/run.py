"""Run one benchmark cell on the chip and print its result line.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout that holds the program (``src/repro``).  The cell
is an entry of ``workloads`` in ``BENCHMARK.json``; its configuration file,
its traffic file (``chipbench/traffic/<traffic>.json``), the driver that file
names (``chipbench/drivers/<driver>.py``), its limits
(``chipbench/limits/<cell>.json``) and its per-layer metric readers
(``chipbench/metrics/<metric>.py``) are found by name.

The run checks that JAX sees a TPU with as many chips as the cell asks for
(otherwise it exits non-zero and prints no result), builds the inputs from
the seed and warms every program the window uses (set-up), measures for
``--seconds``, reads the peak device memory, frees the program's state and
compares what the window produced with the plain reference.  With
``--trace 1`` the window runs under the profiler and the per-layer metrics
are reported instead of the end-to-end ones.

The last line of standard output is one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (with
``--trace 1`` also ``breakdown``) and last ``checks``: each compared number
beside its limit.  The same numbers are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse        # noqa: E402
import importlib.util  # noqa: E402
import json            # noqa: E402
import os              # noqa: E402
import shutil          # noqa: E402
import sys             # noqa: E402
import tempfile        # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SPAN = "chipbench."


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_check(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devs[0].platform!r}, not a TPU; "
                     f"the benchmark runs on the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def use_program(root: Path) -> None:
    """Put the checkout's program first on the import path."""
    src = root / "src"
    if not (src / "repro" / "cluster" / "__init__.py").is_file():
        raise FileNotFoundError(f"no program under {src}: run from the root "
                                f"of a checkout of the repository")
    sys.path.insert(0, str(src))


def enable_compile_cache(root: Path) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where set, else ``<root>/.jax_cache``;
    every program is cached, however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Counts XLA compilations and persistent-cache hits while open."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.compiles, self.seconds, self.hits = 0, 0.0, 0

    def _on_duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.hits += 1

    def __enter__(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)
        return False


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(SPAN + name)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str) -> tuple:
    """(BENCHMARK.json, the cell's entry, its configuration, its traffic)."""
    bench = load_json(root / "BENCHMARK.json")
    cell = next((c for c in bench["workloads"] if c["name"] == workload),
                None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (bench, cell, load_json(root / config["file"]),
            load_json(root / "chipbench" / "traffic"
                      / f"{cell['traffic']}.json"))


def metrics_for(entries: list, workload: str) -> list:
    return [m for m in entries
            if "workloads" not in m or workload in m["workloads"]]


def load_module(root: Path, kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` under ``root``, loaded by its path."""
    path = root / "chipbench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_metric(root: Path, name: str, record: dict):
    """The value a metric's reader finds in the run, or None."""
    return load_module(root, "metrics", name).read(record)


def run(root: Path, workload: str, seed: int, seconds: float,
        trace: bool) -> dict:
    bench, cell, cfg, traffic = load_cell(root, workload)
    device = device_check(int(cell["chips"]))
    use_program(root)
    log(f"compile_cache={enable_compile_cache(root)}")
    from chipbench import check
    from chipbench.trace import Trace

    limits = check.load_limits(root, workload)
    driver = load_module(root, "drivers", traffic["driver"]).Driver(
        cfg, traffic, seed, span, log)
    try:
        with CompileClock() as setup_clock:
            driver.setup(seconds)
        log(f"setup compiles={setup_clock.compiles} "
            f"compile_s={setup_clock.seconds:.3f} "
            f"cache_hits={setup_clock.hits}")
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace \
            else None
        setup_s = time.perf_counter() - T_START
        import jax

        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        try:
            with CompileClock() as clock, span("window"):
                driver.window(seconds)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        peak = memory_peak_bytes()
        log(f"window compiles={clock.compiles} "
            f"compile_s={clock.seconds:.3f} cache_hits={clock.hits} "
            f"peak_bytes_per_device="
            f"{[(d.memory_stats() or {}).get('peak_bytes_in_use') for d in jax.devices()]}")
        e2e = {"setup_s": setup_s, **driver.end_to_end()}
        attempted, failed = driver.counts()
        record = driver.record()
        driver.release()
        t_check = time.perf_counter()
        numbers = driver.numbers()
        log(f"setup_s={setup_s!r} check_s={time.perf_counter() - t_check:.3f}")
    finally:
        driver.close()
    correct, checks = check.judge(numbers, limits)

    device["memory_peak_bytes"] = peak
    out = {"correct": correct, "attempted": attempted, "failed": failed}
    if trace_dir:
        tr = Trace.from_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        record.update(trace=tr, device_kind=device["kind"],
                      window=tr.window() if tr else None)
        metrics = {}
        for m in metrics_for(bench["per_layer"], workload):
            value = read_metric(root, m["name"], record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = metrics
        w = record["window"]
        if tr is not None and w is not None:
            device["busy_s"] = tr.busy_s(*w)
            device["window_s"] = (w[1] - w[0]) * 1e-9
            out["breakdown"] = {"device_ops": tr.top_ops(*w),
                                "idle_gaps": tr.idle_gaps(*w)}
    else:
        out["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in metrics_for(bench["end_to_end"], workload)}
    out["device"] = device
    out["checks"] = checks
    return out


def main(argv=None, root: Path | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(root or ROOT)
    try:
        out = run(root, args.workload, args.seed, args.seconds,
                  bool(args.trace))
    except NoChip as e:
        log(f"error: {e}")
        return 2
    print(json.dumps(out), flush=True)
    from chipbench.check import print_checks

    print_checks(out["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())

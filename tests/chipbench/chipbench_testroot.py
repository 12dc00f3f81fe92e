"""A benchmark root at CPU size for the benchmark's own tests.

``make_root(tmp)`` lays out what the harness reads (``BENCHMARK.json``, the
configuration, traffic, driver, limits and metric files) under ``tmp`` with the
tiny configuration of ``fixtures/``, links the program in as ``src``, and
returns the root.  ``run_cell`` drives one cell through the harness with the
chip check replaced by a stand-in, so the rest of a run is real."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY_FIT = "tiny.fit"


def make_root(tmp: Path) -> Path:
    root = Path(tmp) / "root"
    (root / "chipbench").mkdir(parents=True)
    for d in ("configs", "drivers", "traffic", "limits", "metrics"):
        shutil.copytree(REPO / "chipbench" / d, root / "chipbench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(FIXTURES / "tiny.json", root / "chipbench" / "configs")
    shutil.copy(FIXTURES / "tiny_fit.json", root / "chipbench" / "traffic")
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "tests",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "CPU size"})
    bench["workloads"].append(
        {"name": TINY_FIT, "config": "tiny", "traffic": "tiny_fit",
         "chips": 1, "why": "CPU size"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        ws = m.get("workloads")
        if ws is not None and any(w.endswith(".fit") for w in ws):
            ws.append(TINY_FIT)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    limits = root / "chipbench" / "limits"
    shutil.copy(limits / "pubmed8m.fit.json", limits / f"{TINY_FIT}.json")
    return root


def fake_device(chips: int) -> dict:
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def run_cell(monkeypatch, root: Path, workload: str, *, seed: int = 7,
             seconds: float = 0.5, trace: bool = False) -> dict:
    from chipbench import run

    monkeypatch.setattr(run, "device_check", fake_device)
    return run.run(root, workload, seed, seconds, trace)

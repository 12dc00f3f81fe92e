"""The command and ``BENCHMARK.json`` against the benchmark's contract."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench_testroot import REPO, fake_device
from chipbench import run

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_command_exits_nonzero_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        BENCH["command"] + ["--workload", "pubmed8m.fit", "--seed", "1",
                            "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "not a TPU" in p.stderr


def test_benchmark_alone_without_the_program_fails(tmp_path, monkeypatch):
    for path in BENCH["paths"]:
        shutil.copytree(REPO / path, tmp_path / path)
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    monkeypatch.setattr(run, "device_check", fake_device)
    with pytest.raises(FileNotFoundError):
        run.run(tmp_path, "pubmed8m.fit", 1, 1.0, False)


def test_top_level_keys_and_command():
    assert list(BENCH) == ["command", "paths", "run_seconds", "configs",
                           "workloads", "end_to_end", "per_layer"]
    assert 1 <= len(BENCH["command"]) <= 32
    assert not any(w.startswith("/") or ".." in w for w in BENCH["command"])
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and (REPO / p).is_dir()
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # A full check of 24 cells fits its time.
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_configs_and_cells():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["why"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        cfg = json.loads((REPO / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) or k == "vocab"
                       for k in c["reduced"])
    pairs = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        root = REPO / "chipbench"
        traffic = json.loads((root / "traffic" / f"{w['traffic']}.json")
                             .read_text())
        assert (root / "drivers" / f"{traffic['driver']}.py").is_file()
        assert (root / "limits" / f"{w['name']}.json").is_file()
    assert set(names) == {w["config"] for w in BENCH["workloads"]}


def _reported(entry, cell):
    return "workloads" not in entry or cell in entry["workloads"]


def test_metrics_name_the_metric_they_move_in_every_cell():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] == 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert (REPO / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
        for cell in m["workloads"]:
            assert _reported(e2e[m["moves"]], cell), (m["name"], cell)
    for w in BENCH["workloads"]:
        reported = [m for m in BENCH["end_to_end"] if _reported(m, w["name"])]
        assert len(reported) >= 2
        assert any(_reported(m, w["name"]) for m in BENCH["per_layer"])

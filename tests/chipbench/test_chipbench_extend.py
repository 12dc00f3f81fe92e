"""A new configuration, traffic mix, driver and per-layer metric join the
benchmark as new files plus new ``BENCHMARK.json`` entries, with no file that
exists edited."""
import hashlib
import json
import shutil

from chipbench_testroot import FIXTURES, make_root, run_cell

EXTENSION = FIXTURES / "extension"


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file() and ".jax_cache" not in
            p.parts and p.name != "BENCHMARK.json"}


def test_cell_added_from_a_fixture_directory(tmp_path, monkeypatch):
    root = make_root(tmp_path)
    before = _digests(root)
    for kind in ("configs", "drivers", "traffic", "metrics", "limits"):
        for f in (EXTENSION / kind).iterdir():
            dest = root / "chipbench" / kind / f.name
            assert not dest.exists()
            shutil.copy(f, dest)
    entries = json.loads((EXTENSION / "entries.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "per_layer"):
        bench[key] += entries[key]
    for m in bench["end_to_end"]:
        m.get("workloads", []).extend(
            entries["end_to_end_workloads"].get(m["name"], []))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _digests(root)
    assert all(after[p] == d for p, d in before.items())

    cell = entries["workloads"][0]["name"]
    plain = run_cell(monkeypatch, root, cell, seconds=0.2)
    assert plain["correct"] is True
    # The new driver runs its two fits, not as many as the window holds.
    assert plain["attempted"] == 2
    assert set(plain["metrics"]) == {"setup_s", "fit_s_per_iter"}
    traced = run_cell(monkeypatch, root, cell, seconds=0.2, trace=True)
    assert traced["metrics"]["fit.count"] == {"value": 2.0, "unit": "fits"}
    assert "lloyd.fused_s_per_iter" not in traced["metrics"]

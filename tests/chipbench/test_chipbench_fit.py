"""A fit cell driven through the whole harness at CPU size: the result
line, its metrics, and the comparison with the reference."""
import json

import pytest

from chipbench_testroot import TINY_FIT, fake_device, make_root, run_cell
from chipbench import run

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("fit"))


def test_result_line_is_the_last_line_with_the_contract_keys(
        root, monkeypatch, capsys):
    monkeypatch.setattr(run, "device_check", fake_device)
    assert run.main(["--workload", TINY_FIT, "--seed", str(2**31 + 11),
                     "--seconds", "0.5", "--trace", "0"], root=root) == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == KEYS + ["checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"setup_s", "fit_s_per_iter"}
    for m in line["metrics"].values():
        assert m["value"] > 0
    assert line["metrics"]["fit_s_per_iter"]["unit"] == "s/iter"
    assert set(line["checks"]) == {"label_share", "update_gap",
                                   "objective_gap"}
    tail = err.strip().splitlines()[-3:]
    assert all(t.startswith("check ") and " limit=" in t for t in tail)


def test_traced_run_reports_the_per_layer_metrics(root, monkeypatch):
    out = run_cell(monkeypatch, root, TINY_FIT, trace=True)
    assert list(out) == KEYS[:4] + ["breakdown", "device", "checks"]
    assert out["correct"] is True
    # On the CPU there is no device plane: only the program's own spans.
    assert set(out["metrics"]) == {"lloyd.prologue_s_per_iter",
                                   "lloyd.fused_s_per_iter"}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_reference_agrees_exactly_at_cpu_size(root, monkeypatch):
    out = run_cell(monkeypatch, root, TINY_FIT, seed=3)
    c = {k: v["value"] for k, v in out["checks"].items()}
    assert c["label_share"] == 0.0
    assert c["update_gap"] < 1e-6 and c["objective_gap"] < 1e-6

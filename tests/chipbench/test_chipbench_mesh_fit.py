"""The K-sharded mesh fit cell driven through the whole harness at CPU size,
on four of the test process's CPU devices: the result line, its per-layer
metrics, the comparison with the K-blocked reference, and faults in the
timed path that the comparison must catch."""
import json

import pytest

from chipbench_testroot import REPO, make_root, run_cell
from chipbench.trace import Device, Trace

TINY_MESH = "tiny.mesh_fit"
NEW_METRICS = {"mesh.step_s_per_iter", "mesh.estparams_s_per_fit",
               "mesh_roofline", "mesh.collective_share"}


def make_mesh_root(tmp):
    """``make_root`` plus the tiny configuration (K=12, three means per
    block) under the mesh traffic, in every list the mesh cell is in."""
    root = make_root(tmp)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": TINY_MESH, "config": "tiny",
                               "traffic": "mesh_fit", "chips": 4,
                               "why": "CPU size"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "nyt1m.mesh_fit" in m.get("workloads", ()):
            m["workloads"].append(TINY_MESH)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    limits = root / "chipbench" / "limits"
    (limits / f"{TINY_MESH}.json").write_text(
        (REPO / "chipbench" / "limits" / "nyt1m.mesh_fit.json").read_text())
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_mesh_root(tmp_path_factory.mktemp("mesh_fit"))


@pytest.fixture
def fresh_steps():
    """A broken step must be traced anew and must not outlive the test:
    the mesh step is built once per geometry."""
    from repro.distributed import kmeans

    kmeans.make_step_fn.cache_clear()
    yield
    kmeans.make_step_fn.cache_clear()


def with_device_planes(real_from_dir):
    """``Trace.from_dir`` with four device planes added to the CPU trace,
    which has none: in each traced fit one ``jit_mesh_step`` per iteration,
    a tenth of it an all-reduce, laid out evenly over the fit's span."""
    def from_dir(directory):
        tr = real_from_dir(directory)
        fits = tr.span_intervals("chipbench.fit")
        devices = []
        for d in range(4):
            ops, modules = [], []
            for s, e in fits:
                n = 8
                step = (e - s) // n
                for i in range(n):
                    ms = s + i * step
                    modules.append((f"jit_mesh_step({d})", ms, ms + step))
                    ops.append(("fusion.1 f32[1024,3]", ms, ms + step))
                    ops.append(("pmax.2 f32[1024]", ms, ms + step // 10))
            devices.append(Device(name=f"/device:TPU:{d}", ops=ops,
                                  modules=modules))
        return Trace(devices=devices, spans=tr.spans)
    return from_dir


def test_untraced_run_is_correct_and_reports_the_fit_metrics(root,
                                                              monkeypatch):
    out = run_cell(monkeypatch, root, TINY_MESH, seed=2**31 + 5)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "fit_s_per_iter"}
    assert out["metrics"]["fit_s_per_iter"]["value"] > 0
    c = {k: v["value"] for k, v in out["checks"].items()}
    assert c["label_share"] == 0.0
    assert c["update_gap"] < 1e-6 and c["objective_gap"] < 1e-6


def test_traced_run_reports_the_mesh_metrics(root, monkeypatch):
    monkeypatch.setattr(Trace, "from_dir",
                        with_device_planes(Trace.from_dir))
    out = run_cell(monkeypatch, root, TINY_MESH, trace=True)
    assert out["correct"] is True
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(metrics) == NEW_METRICS | {"device.idle_share.fit"}
    assert all(v is not None for v in metrics.values())
    assert metrics["mesh.step_s_per_iter"] > 0
    assert metrics["mesh.estparams_s_per_fit"] > 0
    assert 0 < metrics["mesh_roofline"] <= 100
    assert metrics["mesh.collective_share"] == pytest.approx(10, rel=0.01)


def test_readers_find_nothing_in_a_fit_without_mesh_counters(root):
    """On a program whose mesh history lacks the counters, as before they
    existed, each reader returns None and raises nothing."""
    from chipbench import run

    record = {"fits": [{"seconds": 1.0, "n_iter": 3, "history": [
        {"iteration": i, "objective": 1.0} for i in (1, 2, 3)]}],
              "trace": None, "window": None}
    for name in NEW_METRICS:
        assert run.read_metric(root, name, record) is None


def test_one_label_altered_is_not_correct(root, monkeypatch, fresh_steps):
    from repro.distributed import kmeans

    real = kmeans.mesh_fit

    def altered(*args, **kw):
        state, history, converged, params = real(*args, **kw)
        k = args[1]
        assign = state.assign.at[0].set((state.assign[0] + 1) % k)
        return (kmeans.DistKMeansState(**{**vars(state), "assign": assign}),
                history, converged, params)

    monkeypatch.setattr(kmeans, "mesh_fit", altered)
    out = run_cell(monkeypatch, root, TINY_MESH, seconds=0.2)
    assert out["correct"] is False
    assert out["checks"]["update_gap"]["value"] > \
        out["checks"]["update_gap"]["limit"]


def test_update_skipped_is_not_correct(root, monkeypatch, fresh_steps):
    from repro.core import meanindex

    monkeypatch.setattr(meanindex, "normalized_means",
                        lambda lam, fallback_t: fallback_t.T)
    out = run_cell(monkeypatch, root, TINY_MESH, seconds=0.2)
    assert out["correct"] is False

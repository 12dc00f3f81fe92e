"""The trace reduction and the per-layer metric readers, on hand-built
traces and records whose numbers are known."""
import math

import pytest

from chipbench_testroot import REPO, make_root  # noqa: F401  (sys.path)
from chipbench.run import read_metric
from chipbench.trace import Trace

NS = 1e-9

# Two devices over a window [0, 100) ns.  Device 0 runs ops at [0, 10),
# [10, 20) and [30, 40): busy 30 ns; device 1 runs [50, 90): busy 40 ns.
SMALL = {
    "devices": [
        {"name": "/device:TPU:0",
         "ops": [["fusion.1", 0, 10], ["copy.2", 10, 20], ["fusion.1", 30, 40]],
         "modules": [["jit_a", 0, 20], ["jit_b", 30, 40]]},
        {"name": "/device:TPU:1",
         "ops": [["fusion.1", 50, 90]], "modules": []},
    ],
    "spans": [["chipbench.window", 0, 100], ["chipbench.seed", 18, 32],
              ["chipbench.fit", 0, 100], ["chipbench.pull", 40, 60]],
}


def test_busy_idle_and_ops_of_a_hand_built_trace():
    tr = Trace.from_dict(SMALL)
    assert tr.window() == (0, 100)
    assert tr.busy_s(0, 100) == pytest.approx(35 * NS)
    assert tr.idle_share(0, 100) == pytest.approx(1 - 35 / 100)
    # Clipped to [5, 35): device 0 busy [5, 20) and [30, 35), device 1 not.
    assert tr.busy_s(5, 35) == pytest.approx((20 + 0) / 2 * NS)
    assert tr.top_ops(0, 100) == [["fusion.1", pytest.approx(30 * NS)],
                                  ["copy.2", pytest.approx(5 * NS)]]


def test_idle_gaps_are_named_by_the_innermost_span():
    tr = Trace.from_dict(SMALL)
    gaps = tr.idle_gaps(0, 100)
    # Device 0 idles [20, 30), [40, 100): the longest first.
    assert gaps[0] == ["chipbench.fit", pytest.approx(60 * NS)]
    assert gaps[1] == ["chipbench.seed", pytest.approx(10 * NS)]
    assert len(gaps) == 2


def test_nested_ops_count_self_time_once():
    tr = Trace.from_dict({"devices": [{"name": "d", "ops": [
        ["while.1", 0, 100], ["fusion.2", 10, 40], ["fusion.3", 50, 60],
        ["copy.4", 120, 130]]}], "spans": []})
    assert tr.top_ops(0, 200) == [["while.1", pytest.approx(60 * NS)],
                                  ["fusion.2", pytest.approx(30 * NS)],
                                  ["fusion.3", pytest.approx(10 * NS)],
                                  ["copy.4", pytest.approx(10 * NS)]]


def test_short_names_keep_the_op_and_its_shape():
    from chipbench.trace import short_name

    assert short_name("%fusion.98 = f32[4096,4096]{1,0:T(8,128)S(1)} "
                      "fusion(f32[141043,4096]{1,0} %g), kind=kCustom") == \
        "fusion.98 f32[4096,4096]"
    assert short_name("jit_loop(123)") == "jit_loop(123)"


def test_merging_of_nested_and_touching_intervals():
    tr = Trace.from_dict({"devices": [{"name": "d", "ops": [
        ["w", 0, 100], ["a", 10, 20], ["b", 100, 110], ["c", 200, 210]]}],
        "spans": []})
    iv = tr.busy_intervals(tr.devices[0], 0, 300)
    assert iv.tolist() == [[0, 110], [200, 210]]


def _fit_record(trace=None):
    hist = [{"iteration": i, "elapsed_s": s}
            for i, s in [(1, 6.0), (2, 5.0), (3, 4.0), (4, 4.0)]]
    return {"fits": [{"seconds": 19.0, "n_iter": 4, "history": hist}],
            "corpus": {"n_docs": 131_072, "pad_width": 128,
                       "dim": 141_043, "nnz_total": 131_072 * 59},
            "k": 4096, "trace": trace, "device_kind": "TPU v5 lite",
            "window": trace.window() if trace else None}


def test_lloyd_span_readers(tmp_path):
    root = make_root(tmp_path)
    rec = _fit_record()
    assert read_metric(root, "lloyd.prologue_s_per_iter", rec) == 5.5
    assert read_metric(root, "lloyd.fused_s_per_iter", rec) == 4.0
    # Without a trace the device readers find nothing to read.
    for name in ("lloyd_roofline", "device.idle_share.fit"):
        assert read_metric(root, name, rec) is None


def test_lloyd_roofline_reads_the_fused_executable(tmp_path):
    root = make_root(tmp_path)
    s = 1e9                                       # ns per second
    tr = Trace.from_dict({
        "devices": [{"name": "/device:TPU:0",
                     "ops": [["while.1", 1 * s, 9 * s]],
                     "modules": [["jit__fused_epoch", 0, 1 * s],
                                 ["jit__unknown", 1 * s, 9 * s]]}],
        "spans": [["chipbench.window", 0, 10 * s],
                  ["chipbench.fit", 0, 10 * s]]})
    rec = _fit_record(tr)
    # Least time of pubmed8m's iteration: 9,378,136,064 bytes at 819 GB/s;
    # the fused executable ran 8 s for 2 fused iterations.
    least = 9_378_136_064 / 819e9
    got = read_metric(root, "lloyd_roofline", rec)
    assert got == pytest.approx(100 * least / 4.0)
    assert read_metric(root, "device.idle_share.fit", rec) == \
        pytest.approx(20.0)


def test_readers_never_invent_a_zero_share(tmp_path):
    root = make_root(tmp_path)
    empty = Trace.from_dict({"devices": [], "spans": []})
    rec = _fit_record(empty)
    rec["window"] = (0, 100)
    for name in ("lloyd_roofline", "device.idle_share.fit"):
        v = read_metric(root, name, rec)
        assert v is None or (math.isfinite(v) and v > 0)

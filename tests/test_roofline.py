"""Roofline plumbing: HLO collective parser + per-device cost semantics."""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.roofline.analysis import (PEAKS, V5E, collective_bytes, cost_dict,
                                     roofline_terms)
from repro.launch.mesh import make_test_mesh


def test_collective_parser_on_crafted_hlo():
    hlo = """
  %ar = f32[128,256]{1,0} all-reduce(%x), replica_groups={}
  %ag.1 = bf16[64]{0} all-gather(%y), dimensions={0}
  %rs = (f32[32,32]{1,0}, f32[32,32]{1,0}) reduce-scatter(%a, %b), dimensions={0}
  %cp = u8[1024]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %done = f32[128,256]{1,0} all-reduce-done(%ar)
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 128 * 256 * 4
    assert out["all-gather"] == 64 * 2
    assert out["reduce-scatter"] == 2 * 32 * 32 * 4
    assert out["collective-permute"] == 1024
    assert out["total"] == sum(v for k, v in out.items()
                               if k not in ("total", "count"))
    assert out["count"] == 4      # -done not double counted


def test_cost_analysis_is_per_device():
    """2·M·N·K flops split across the model axis -> per-device count."""
    mesh = make_test_mesh((2, 4), ("data", "model"))
    m, n, k = 64, 256, 512

    def f(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((m, k), jnp.float32)
    b = jax.ShapeDtypeStruct((k, n), jnp.float32)
    with mesh:
        compiled = jax.jit(
            f,
            in_shardings=(NamedSharding(mesh, P("data", None)),
                          NamedSharding(mesh, P(None, "model"))),
        ).lower(a, b).compile()
    flops = cost_dict(compiled.cost_analysis())["flops"]
    total = 2 * m * n * k
    assert abs(flops - total / 8) / (total / 8) < 0.05


def test_roofline_terms_and_bottleneck():
    cost = {"flops": 197e12, "bytes accessed": 819e9 / 2}
    coll = {"total": 50e9 * 2}
    t = roofline_terms(cost, coll, PEAKS[V5E])
    assert abs(t["t_compute_s"] - 1.0) < 1e-9
    assert abs(t["t_memory_s"] - 0.5) < 1e-9
    assert abs(t["t_collective_s"] - 2.0) < 1e-9
    assert t["bottleneck"] == "collective"
    assert abs(t["roofline_frac_compute"] - 0.5) < 1e-9


def test_scan_undercount_is_corrected_by_unroll():
    """The reason the dry-run costing pass exists (launch/dryrun.py)."""
    from repro.models.config import set_scan_unroll, scan_unroll

    def scanned(ws, x):
        y, _ = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws,
                            unroll=scan_unroll())
        return y.sum()

    ws = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    f_loop = cost_dict(jax.jit(scanned).lower(ws, x).compile()
                       .cost_analysis())["flops"]
    set_scan_unroll(True)
    try:
        # fresh trace — the flag is read at trace time, so the cached
        # unroll=False trace must not be reused (the dry-run rebuilds its
        # step closures per pass for exactly this reason)
        jax.clear_caches()
        f_unroll = cost_dict(jax.jit(scanned).lower(ws, x).compile()
                             .cost_analysis())["flops"]
    finally:
        set_scan_unroll(False)
        jax.clear_caches()
    assert f_unroll > 3.5 * f_loop   # 4 bodies vs 1


def test_peaks_are_keyed_by_device_kind():
    import pytest

    from repro.roofline import peaks

    v5e = peaks(V5E)
    assert (v5e.peak_flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9,
                                                           16e9)
    assert "TPU v5e" in v5e.source
    # The CPU entry exists for the tuner's rehearsals and says so.
    assert "not a measured CPU peak" in PEAKS["cpu"].source
    assert peaks() is PEAKS[jax.devices()[0].device_kind]
    with pytest.raises(ValueError, match="TPU v9"):
        peaks("TPU v9")

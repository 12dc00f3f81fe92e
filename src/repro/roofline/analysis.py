"""Roofline-term extraction from compiled dry-run artifacts.

    compute term    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory term     = HLO_bytes / (chips × HBM_bw)
    collective term = collective_bytes / (chips × link_bw)

``compiled.cost_analysis()`` on the host backend reports *per-device*
FLOPs/bytes for the SPMD program (verified against hand counts in
tests/test_roofline.py), so the per-chip terms divide by peak only.
collective_bytes is parsed from the optimized HLO: operand bytes of every
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute.
"""
from __future__ import annotations

import dataclasses
import re


def cost_dict(cost) -> dict:
    """``compiled.cost_analysis()`` as a plain dict ({} when absent)."""
    return dict(cost or {})


@dataclasses.dataclass(frozen=True)
class HW:
    """Peak rates of one chip, with where they come from."""
    peak_flops: float              # bf16 FLOP/s
    hbm_bw: float                  # B/s
    hbm_bytes: float               # device memory, bytes
    ici_bw: float                  # B/s per link
    source: str


# The device kind JAX reports for a TPU v5e chip.
V5E = "TPU v5 lite"

# Peak table keyed by ``jax.Device.device_kind``.  A kind that is not here
# is an error (``peaks``), never a silent default.
PEAKS: dict[str, HW] = {
    V5E: HW(peak_flops=197e12, hbm_bw=819e9, hbm_bytes=16e9, ici_bw=50e9,
            source="Google Cloud, TPU v5e: 197 TFLOP/s bf16, 819 GB/s and "
                   "16 GB HBM, 1,600 Gbit/s ICI (4 links)"),
    # Not a CPU peak.  The autotuner's analytic pruning (tune/cost.py) runs
    # on CPU rehearsals too; it ranks candidates with the v5e rates there,
    # so CPU runs prune the way a chip run would.
    "cpu": HW(peak_flops=197e12, hbm_bw=819e9, hbm_bytes=16e9, ici_bw=50e9,
              source="v5e rates reused for CPU rehearsals of the "
                     "autotuner's pruning; not a measured CPU peak"),
}


def peaks(device_kind: str | None = None) -> HW:
    """The peak rates of ``device_kind`` (default: the first JAX device)."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    if device_kind not in PEAKS:
        raise ValueError(f"no peak rates for device kind {device_kind!r}; "
                         f"add them, with their source, to "
                         f"repro.roofline.analysis.PEAKS")
    return PEAKS[device_kind]


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", )
_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes per collective kind from optimized HLO text.

    '-start' ops are counted, '-done' duplicates are skipped (async pairs).
    Returns {kind: bytes, ..., 'total': bytes, 'count': n}.
    """
    out: dict = {}
    count = 0
    for line in hlo_text.splitlines():
        if "-done(" in line or "-done.{" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        out[kind] = out.get(kind, 0) + b
        count += 1
    out["total"] = sum(v for k, v in out.items() if k != "total")
    out["count"] = count
    return out


def roofline_terms(cost: dict, coll: dict, hw: HW) -> dict:
    """Per-chip roofline seconds (cost_analysis is already per-device)."""
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    cbytes = float(coll.get("total", 0))
    t_compute = flops / hw.peak_flops
    t_memory = bytes_acc / hw.hbm_bw
    t_coll = cbytes / hw.ici_bw
    terms = {"t_compute_s": t_compute, "t_memory_s": t_memory,
             "t_collective_s": t_coll,
             "flops_per_dev": flops, "bytes_per_dev": bytes_acc,
             "coll_bytes_per_dev": cbytes}
    dom = max(("compute", t_compute), ("memory", t_memory),
              ("collective", t_coll), key=lambda kv: kv[1])
    terms["bottleneck"] = dom[0]
    bound = max(t_compute, t_memory, t_coll)
    terms["roofline_frac_compute"] = (t_compute / bound) if bound > 0 else 0.0
    return terms


def model_flops(cfg, shape, n_chips: int) -> dict:
    """MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE), D = tokens.

    For decode shapes D = batch tokens (one step).  Returns per-device
    numbers for direct comparison with cost_analysis flops."""
    n_active = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.batch * shape.seq
        factor = 6.0
    elif shape.kind == "prefill":
        tokens = shape.batch * shape.seq
        factor = 2.0
    else:  # decode: one token per sequence
        tokens = shape.batch
        factor = 2.0
    total = factor * n_active * tokens
    return {"model_flops_total": total,
            "model_flops_per_dev": total / n_chips}

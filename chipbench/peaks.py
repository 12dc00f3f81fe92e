"""Peak rates of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error, never a
default: a roofline share against the wrong peak is no measurement."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_per_s: float           # bf16 matrix unit
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS: dict[str, Peak] = {
    "TPU v5 lite": Peak(
        flops_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip"),
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"add its row, with its source, to "
                       f"chipbench/peaks.py") from None

from repro.roofline.analysis import (
    HW,
    PEAKS,
    V5E,
    collective_bytes,
    model_flops,
    peaks,
    roofline_terms,
)

__all__ = ["HW", "PEAKS", "V5E", "collective_bytes", "model_flops", "peaks",
           "roofline_terms"]

"""Share of the least time reached by one exact Lloyd iteration on the chip.

Least time: ``chipbench.roofline`` from N, P, the live terms, D and K, the
same work whatever engine runs it.  Device time per fused iteration: in each
traced fit (harness span ``chipbench.fit``), the longest executable that ran
on the device is the fused remainder of the fit, which runs every iteration
after the EstParams prologue in one call; their total over the fused
iterations.  Says on standard error which bound binds."""
import sys

from chipbench import peaks, roofline

PROLOGUE = 2


def read(record):
    tr = record.get("trace")
    fits = record.get("fits")
    if tr is None or not fits:
        return None
    spans = tr.span_intervals("chipbench.fit")
    if len(spans) != len(fits):
        return None
    device_ns, iters = 0.0, 0
    for (s, e), f in zip(spans, fits):
        mods = tr.modules_in(s, e)
        n_fused = f["n_iter"] - PROLOGUE
        if not mods or n_fused < 1:
            continue
        name, ms, me = max(mods, key=lambda m: m[2] - m[1])
        device_ns += me - ms
        iters += n_fused
    if iters == 0:
        return None
    c = record["corpus"]
    least = roofline.least_time(
        roofline.lloyd_iteration(n_docs=c["n_docs"], pad_width=c["pad_width"],
                                 nnz_total=c["nnz_total"], dim=c["dim"],
                                 k=record["k"]),
        peaks.peak(record["device_kind"]))
    per_iter_s = device_ns * 1e-9 / iters
    print(f"lloyd_roofline bound={least['bound']} least_s={least['least_s']!r}"
          f" device_s_per_fused_iter={per_iter_s!r}", file=sys.stderr)
    return 100.0 * least["least_s"] / per_iter_s

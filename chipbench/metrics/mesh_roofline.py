"""Share of one chip's least time reached by a steady K-sharded mesh step.

Least time: ``chipbench.roofline.lloyd_iteration`` of the work one chip
does in a step: its rows of the corpus (all of them on a 'data' axis of 1)
against its K/|model| means.  Device time: on each chip, the executables
named ``jit_mesh_step`` inside each traced fit (harness span
``chipbench.fit``), one per iteration in order; those after the two
EstParams iterations, over their count, averaged over the chips.  Says on
standard error which bound binds."""
import sys

from chipbench import peaks, roofline

EST_ITERS = 2
STEP = "jit_mesh_step"


def read(record):
    tr, fits, mesh = record.get("trace"), record.get("fits"), \
        record.get("mesh")
    if tr is None or not fits or not mesh or not tr.devices:
        return None
    spans = tr.span_intervals("chipbench.fit")
    if len(spans) != len(fits):
        return None
    per_chip = []
    for dev in range(len(tr.devices)):
        device_ns, iters = 0.0, 0
        for (s, e), f in zip(spans, fits):
            steps = sorted((m for m in tr.modules_in(s, e, device=dev)
                            if m[0].startswith(STEP)), key=lambda m: m[1])
            steady = steps[EST_ITERS:f["n_iter"]]
            device_ns += sum(me - ms for _, ms, me in steady)
            iters += len(steady)
        if iters:
            per_chip.append(device_ns * 1e-9 / iters)
    if not per_chip:
        return None
    c, data = record["corpus"], int(mesh["data"])
    least = roofline.least_time(
        roofline.lloyd_iteration(n_docs=c["n_docs"] // data,
                                 pad_width=c["pad_width"],
                                 nnz_total=c["nnz_total"] // data,
                                 dim=c["dim"],
                                 k=record["k"] // int(mesh["model"])),
        peaks.peak(record["device_kind"]))
    per_iter_s = sum(per_chip) / len(per_chip)
    print(f"mesh_roofline bound={least['bound']} least_s={least['least_s']!r}"
          f" device_s_per_steady_step={per_iter_s!r}", file=sys.stderr)
    return 100.0 * least["least_s"] / per_iter_s

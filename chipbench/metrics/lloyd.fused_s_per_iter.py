"""Seconds per Lloyd iteration in the fused ``while_loop`` remainder.

The program's history gives each fused iteration the mean of the one fused
call (``elapsed_s`` of iterations 3 and later); averaged over the fits in
the window."""

PROLOGUE = (1, 2)


def read(record):
    rows = [h["elapsed_s"] for f in record.get("fits", ())
            for h in f["history"] if h["iteration"] not in PROLOGUE]
    return sum(rows) / len(rows) if rows else None

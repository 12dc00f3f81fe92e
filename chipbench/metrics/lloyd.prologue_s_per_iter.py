"""Seconds per Lloyd iteration in the host-stepped EstParams prologue.

The program's own host-clock time of iterations 1 and 2 of each fit in the
window (``FittedModel.history[i]["elapsed_s"]``), averaged over them."""

PROLOGUE = (1, 2)


def read(record):
    rows = [h["elapsed_s"] for f in record.get("fits", ())
            for h in f["history"] if h["iteration"] in PROLOGUE]
    return sum(rows) / len(rows) if rows else None

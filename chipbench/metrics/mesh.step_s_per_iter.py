"""Seconds per steady iteration of a K-sharded mesh fit.

The program's host clock (``FittedModel.history[i]["elapsed_s"]``: from the
step's call to the end of the iteration's pull) of the iterations after the
EstParams iterations 1 and 2, averaged over the fits in the window."""

EST_ITERS = (1, 2)


def read(record):
    rows = [h.get("elapsed_s") for f in record.get("fits", ())
            for h in f["history"] if h["iteration"] not in EST_ITERS]
    if not rows or None in rows:
        return None
    return sum(rows) / len(rows)

"""Host seconds of EstParams per K-sharded mesh fit.

The program's ``repro.estparams`` span time, as each history row holds its
share of it (``estparams_s``), summed over a fit's rows and averaged over
the fits in the window."""


def read(record):
    fits = record.get("fits", ())
    per_fit = [[h.get("estparams_s") for h in f["history"]] for f in fits]
    if not per_fit or any(not rows or None in rows for rows in per_fit):
        return None
    return sum(sum(rows) for rows in per_fit) / len(per_fit)

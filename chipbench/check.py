"""The comparison that decides ``correct``.

Each cell compares what its timed path produced with the plain reference
(``chipbench.reference``) through a few numbers, each held to a limit of its
own from ``chipbench/limits/<cell>.json``.  Every number is a gap: zero where
the program and the reference agree exactly, larger the further they part.
A number passes when it is finite and at most its limit.

Fits (``fit_numbers``):

- ``label_share``: the share of documents whose final label differs from
  the reference fit's, both started from the same seeded means.
- ``update_gap``: the largest difference between a mean the program
  returned and the normalised sum of the documents its own labels put in
  that cluster (the update step, checked on the program's own output;
  clusters its labels leave empty keep an older mean and are skipped).
- ``objective_gap``: the largest relative difference between the objective
  J after each iteration and the reference's after the same iteration.

A fit that is sound may still part from the reference on a few documents
at a near-tie: ``label_share``'s limit leaves room for them.
"""
from __future__ import annotations

import json
import math
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from chipbench import reference


def fit_numbers(corpus, *, k: int, seed: int, max_iter: int, fits: list,
                means_t, ref=None) -> dict:
    """``fits``: [(labels (N,), objectives per iteration)] of every fit the
    window ran; ``means_t``: the (D, K) means the last of them returned.
    ``ref`` is the reference fit, computed here when not given."""
    ids, vals, nnz = corpus.ids, corpus.vals, corpus.nnz
    labels = np.asarray(fits[-1][0])
    own = reference.means_from_labels(ids, vals, nnz, labels, k, corpus.dim)
    filled = np.bincount(labels, minlength=k) > 0
    diff = jnp.max(jnp.abs(jnp.asarray(means_t, jnp.float32) - own), axis=0)
    update_gap = float(np.max(np.where(filled, np.asarray(diff), 0.0)))
    del own, diff
    if ref is None:
        ref = reference.lloyd(ids, vals, nnz, k=k, dim=corpus.dim, seed=seed,
                              max_iter=max_iter)
    label_share, objective_gap = 0.0, 0.0
    for got, objectives in fits:
        label_share = max(label_share,
                          float(np.mean(np.asarray(got) != ref.labels)))
        if len(objectives) != len(ref.objectives):
            objective_gap = math.inf
            continue
        for j_got, j_ref in zip(objectives, ref.objectives):
            objective_gap = max(objective_gap,
                                abs(j_got - j_ref) / abs(j_ref))
    return {"label_share": label_share, "update_gap": update_gap,
            "objective_gap": objective_gap}


def load_limits(root: Path, workload: str) -> dict:
    """{number: limit} of a cell."""
    path = root / "chipbench" / "limits" / f"{workload}.json"
    with open(path) as f:
        spec = json.load(f)
    return {name: float(entry["limit"]) for name, entry in spec.items()}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number must be finite
    and at most its limit, and every limit must have its number."""
    checks, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name, math.inf)
        passed = math.isfinite(value) and value <= limit
        ok = ok and passed
        checks[name] = {"value": value if math.isfinite(value) else None,
                        "limit": limit}
    return ok, checks


def print_checks(checks: dict) -> None:
    for name, c in checks.items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)

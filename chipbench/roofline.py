"""Least time of one exact Lloyd iteration, whatever engine runs it.

The work is counted from the sizes alone, so the same number serves every
engine and every pruning mode:

- bytes: one read of the corpus as stored (ids and values, N x P each, and
  the N row lengths), one read of the (D, K) mean index, one write and one
  re-read of the (K, D) cluster sums, and one write of the new (D, K) means,
  all 4-byte words;
- operations: the unpruned multiply-adds of assignment (every live term of
  every document against every mean, 2 operations each), plus the update's
  sum (1 per live term) and the self-similarity refresh (2 per live term).

The least time is the larger of bytes over the peak bandwidth and
operations over the peak rate; ``bound`` says which.
"""
from __future__ import annotations

WORD = 4


def lloyd_iteration(*, n_docs: int, pad_width: int, nnz_total: int,
                    dim: int, k: int) -> dict:
    corpus = n_docs * pad_width * 2 * WORD + n_docs * WORD
    index = dim * k * WORD
    return {"bytes": corpus + index + 2 * index + index,
            "flops": 2 * nnz_total * k + 3 * nnz_total}


def least_time(work: dict, peak) -> dict:
    t_bytes = work["bytes"] / peak.hbm_bytes_per_s
    t_flops = work["flops"] / peak.flops_per_s
    return {"least_s": max(t_bytes, t_flops),
            "bound": "memory" if t_bytes >= t_flops else "compute",
            "t_bytes_s": t_bytes, "t_flops_s": t_flops, **work}

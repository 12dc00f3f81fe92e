"""Analytic kernel cost model — the autotuner's pruning oracle.

Mirrors the paper's parameter determination: instead of timing every point
of the knob space, estimate the *approximate work* of each candidate —
FLOPs and bytes of the superblock launch it would produce — and convert the
estimate to a roofline lower bound through the existing
:func:`repro.roofline.analysis.roofline_terms`.  Candidates whose lower
bound already loses to the incumbent's are discarded without ever running;
only the analytically-plausible survivors get wall-clock time.

The model is deliberately coarse (ranking consistency is what pruning
needs, not absolute accuracy):

* densify work: one one-hot walk of ``b_blk × P × d_blk`` compare/FMA lanes
  per *live* grid cell per K-superblock revisit, minus the head-cached
  trailing blocks;
* MXU work: ``2 · b_blk · d_blk · k_sup`` per live cell visit (×2 for the
  two-accumulator ES gather);
* bytes: operand fetches per revisit (ids/vals per superblock pass, the
  means block per B-tile, cached head slabs per visit) plus one output
  write;
* a per-executed-grid-step overhead term — 0 on real hardware, dominant in
  interpret mode, where each step costs Python-level dispatch.  This is
  what lets the same model rank candidates honestly on CPU runners.

A VMEM feasibility gate (``fits_vmem``) removes configs whose blocks cannot
co-reside on a TPU core at all; those count as analytically pruned too.
"""
from __future__ import annotations

import dataclasses

from repro.roofline.analysis import HW, peaks, roofline_terms
from repro.tune.config import TunedConfig

#: TPU-core VMEM budget the resident blocks must fit (bytes, conservative).
VMEM_BUDGET = 16 << 20

#: Per-executed-grid-step dispatch cost of the Pallas interpreter (seconds).
#: Calibration is rough by design — it only needs to dominate the roofline
#: terms the way real interpret-mode dispatch dominates real compute.
INTERPRET_STEP_OVERHEAD = 5e-4

KERNELS = ("sparse_sim", "esicp_gather", "segment_update", "rho_gather")


@dataclasses.dataclass(frozen=True)
class KernelShape:
    """Logical shape of one clustering-kernel call."""
    b: int
    p: int
    d: int
    k: int


def _ceil_to(n: int, m: int) -> int:
    return n + (-n) % m


def launch_geometry(cfg: TunedConfig, shape: KernelShape) -> dict:
    """Padded sizes + grid of the launch ``cfg`` produces at ``shape``."""
    from repro.kernels.ops import _pick_k_sup
    from repro.kernels.plan import pick_n_head

    bp = _ceil_to(shape.b, cfg.b_blk)
    kp = _ceil_to(shape.k, cfg.k_blk)
    dp = _ceil_to(shape.d, cfg.d_blk)
    pp = _ceil_to(shape.p, 8)
    ks = _pick_k_sup(kp, cfg.k_blk, None, cap=cfg.k_sup_cap)
    nd = dp // cfg.d_blk
    n_head = min(nd, pick_n_head(bp, shape.d, d_blk=cfg.d_blk,
                                 head_bytes=cfg.head_bytes))
    return {"bp": bp, "kp": kp, "dp": dp, "pp": pp, "ks": ks,
            "nb": bp // cfg.b_blk, "nk": kp // ks, "nd": nd,
            "n_head": n_head}


def fits_vmem(cfg: TunedConfig, shape: KernelShape, *,
              budget: int = VMEM_BUDGET) -> bool:
    """Can the resident blocks of one grid step co-exist in VMEM?

    slab (+count twin) + means block + two (B, K_sup) accumulators +
    the ids/vals tile + one cached head block.  The XLA-blocked engine has
    no VMEM-resident grid step — XLA tiles its own programs — so every
    config is feasible there.
    """
    if cfg.engine == "xla_blocked":
        return True
    g = launch_geometry(cfg, shape)
    slab = cfg.b_blk * cfg.d_blk * 4 * 2          # value + count twin
    means = cfg.d_blk * g["ks"] * 4
    out = cfg.b_blk * g["ks"] * 4 * 2             # sims + counts
    tuples = cfg.b_blk * g["pp"] * (4 + 4)
    head = (cfg.b_blk * cfg.d_blk * 4 * 2) if g["n_head"] else 0
    return slab + means + out + tuples + head <= budget


def kernel_flops_bytes(kernel: str, cfg: TunedConfig, shape: KernelShape,
                       occ_frac: float) -> tuple[float, float, float]:
    """(flops, bytes, executed_grid_steps) estimate for one kernel launch.

    ``occ_frac`` is the live fraction of (B-tile, D-block) cells at this
    config's geometry (tune/cache.occupancy_fraction); occupancy pruning
    skips the work — but not the grid step — of the dead cells.
    """
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}; one of {KERNELS}")
    if cfg.engine == "xla_blocked":
        return _xla_flops_bytes(kernel, cfg, shape)
    g = launch_geometry(cfg, shape)
    bb, db = cfg.b_blk, cfg.d_blk
    grid_steps = g["nb"] * g["nk"] * g["nd"]
    live_frac = min(1.0, max(float(occ_frac), g["n_head"] / max(g["nd"], 1)))
    live_cells = g["nb"] * g["nd"] * live_frac           # per superblock pass
    live_visits = live_cells * g["nk"]

    # Densify: the one-hot walk, skipped for head-cached trailing blocks.
    head_share = g["n_head"] / max(g["nd"], 1)
    densify_visits = live_visits * max(0.0, 1.0 - head_share)
    densify_flops = densify_visits * bb * g["pp"] * db * 3.0

    # MXU: slab @ means_blk per live visit; the ES gather accumulates two
    # outputs (rho12, y) plus the fused sims off the same slab.
    mxu_per_visit = 2.0 * bb * db * g["ks"]
    mxu_factor = {"sparse_sim": 1.0, "esicp_gather": 2.5,
                  "segment_update": 1.0, "rho_gather": 1.0}[kernel]
    mxu_flops = live_visits * mxu_per_visit * mxu_factor

    tuple_bytes = g["nk"] * g["bp"] * g["pp"] * 8.0      # ids+vals per pass
    means_bytes = 0.0 if kernel == "segment_update" else \
        g["nb"] * g["dp"] * g["kp"] * 4.0                # means per B-tile
    head_bytes_rw = live_visits * head_share * bb * db * 4.0
    out_bytes = {"sparse_sim": g["bp"] * g["kp"] * 4.0,
                 "esicp_gather": 3.0 * g["bp"] * g["kp"] * 4.0,
                 "segment_update": g["kp"] * g["dp"] * 4.0,
                 "rho_gather": g["bp"] * 4.0}[kernel]

    flops = densify_flops + mxu_flops
    nbytes = tuple_bytes + means_bytes + head_bytes_rw + out_bytes
    return flops, nbytes, float(grid_steps)


def _xla_flops_bytes(kernel: str, cfg: TunedConfig,
                     shape: KernelShape) -> tuple[float, float, float]:
    """(flops, bytes, steps) for the gather-formulation XLA engine.

    Work is proportional to *postings*, not the (B, D) grid: each of the
    ``bp·pp`` postings gathers a K-row and folds it (occupancy skipping in
    its limiting form).  A head budget moves the head-share of postings out
    of the gather and into one ``bp × (n_head·d_blk) × kp`` GEMM per call —
    dense FLOPs the matmul units must amortise, which is exactly the
    trade-off the measured pass decides.  ``steps = 0``: the engine always
    compiles, so no interpreter dispatch term applies.
    """
    from repro.kernels.plan import pick_n_head

    bp = float(shape.b)
    kp = float(shape.k)
    pp = float(_ceil_to(shape.p, 8))
    nd = max(1, -(-shape.d // cfg.d_blk))
    n_head = min(nd, pick_n_head(shape.b, shape.d, d_blk=cfg.d_blk,
                                 head_bytes=cfg.head_bytes))
    head_share = n_head / nd
    h = float(n_head * cfg.d_blk)

    if kernel == "segment_update":
        # Scatter-add: one read-modify-write lane per posting.
        flops = bp * pp
        nbytes = bp * pp * 8.0 + shape.k * shape.d * 4.0
        return flops, nbytes, 0.0
    if kernel == "rho_gather":
        flops = 2.0 * bp * pp
        nbytes = bp * pp * 8.0 + bp * pp * 4.0 + bp * 4.0
        return flops, nbytes, 0.0

    acc_factor = {"sparse_sim": 1.0, "esicp_gather": 2.5}[kernel]
    tail_pp = pp * max(0.0, 1.0 - head_share)
    gather_flops = 2.0 * bp * tail_pp * kp * acc_factor
    gemm_flops = 2.0 * bp * h * kp * acc_factor
    gather_bytes = bp * tail_pp * (8.0 + kp * 4.0)     # tuples + K-rows
    head_bytes_r = bp * h * 4.0 * (2.0 if kernel == "esicp_gather" else 1.0)
    means_bytes = float(shape.d) * kp * 4.0
    out_bytes = bp * kp * 4.0 * acc_factor
    return (gather_flops + gemm_flops,
            gather_bytes + head_bytes_r + means_bytes + out_bytes, 0.0)


def lower_bound_seconds(cfg: TunedConfig, shape: KernelShape,
                        occ_frac: float, *, kernels=KERNELS,
                        hw: HW | None = None,
                        step_overhead_s: float = 0.0) -> float:
    """Roofline lower bound on the summed runtime of ``kernels`` under
    ``cfg`` — max(compute term, memory term) via roofline_terms, plus the
    per-step dispatch overhead (interpret-mode platforms)."""
    hw = hw or peaks()
    total = 0.0
    for kernel in kernels:
        flops, nbytes, steps = kernel_flops_bytes(kernel, cfg, shape,
                                                  occ_frac)
        terms = roofline_terms({"flops": flops, "bytes accessed": nbytes},
                               {"total": 0}, hw)
        total += max(terms["t_compute_s"], terms["t_memory_s"])
        total += steps * step_overhead_s
    return total

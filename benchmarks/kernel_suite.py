"""Kernel microbenchmark suite: each clustering kernel vs its pure-jnp
reference op at matched shapes, across BOTH engines — Pallas and the
compiled XLA-blocked twins — tuned vs default vs reference (ISSUE 5
satellite; compiled-mode + autotuner rows from ISSUE 6; xla_blocked rows
and the enforced CPU ratchet from ISSUE 10).

For every kernel — ``sparse_sim``, ``esicp_gather``, ``segment_update``,
``rho_gather`` — seven rows:

    kernel_suite/<name>_reference           the jnp oracle (kernels/ref.py)
    kernel_suite/<name>_pallas              the wrapper, inline occupancy
    kernel_suite/<name>_pallas_planned      the wrapper fed a prepared
                                            KernelPlan (cached head slabs +
                                            precomputed occupancy)
    kernel_suite/<name>_pallas_tuned        the wrapper under the pallas
                                            autotuner winner + matching plan
    kernel_suite/<name>_xla_blocked         kernels/xla_blocked.py, plan-less
                                            gather formulation (the engine
                                            default: head-less)
    kernel_suite/<name>_xla_blocked_planned the XLA twin fed the default-
                                            geometry plan (head slabs ride a
                                            dense GEMM)
    kernel_suite/<name>_xla_blocked_tuned   the XLA twin under its own
                                            engine's autotuner winner

plus ``kernel_suite/autotuner`` / ``kernel_suite/autotuner_xla`` meta-rows
recording what each engine's roofline-pruned search did, and
``kernel_suite/plan_build_*`` rows timing KernelPlan construction
*separately* from the steady-state kernel calls it feeds (plan build is
host-side, once-per-fit work — folding it into a per-call timing would
misprice both).

Execution-mode honesty: the Pallas rows run compiled on a TPU and through
the interpreter everywhere else (``ops.default_interpret``); there is no
fallback, so a kernel the TPU compiler refuses fails the suite there
(DESIGN.md §7).  Every pallas row carries the ``interpret``/``mode``
flags, and cross-mode ratios are suppressed:
``speedup`` (vs the compiled-XLA reference) is null with
``comparable: false`` whenever the Pallas kernels ran interpreted.  The
``xla_blocked`` rows always compile — same mode as the reference on every
platform — so they are ``comparable: true`` everywhere, which is what lets
benchmarks/ratchet.py enforce the compiled speedup gate on the stock CPU
runner.  The ``speedup_vs_default`` field on tuned rows compares two
same-engine, same-mode timings and is therefore always valid (the XLA
engine's default is the plan-less gather row).

Shapes follow the reduced-PubMed regime (Zipf-skewed synthetic corpus →
realistic occupancy); ``REPRO_BENCH_SMOKE=1`` shrinks the shapes AND the
autotuner budget (repro.tune.SearchBudget.default) for CI.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (bench_row, speedup_fields, time_call,
                               time_call_warm)
from repro.kernels import ops, ref
from repro.kernels import xla_blocked as xb
from repro.kernels.plan import prepare_plan
from repro.tune.search import SearchBudget, search_tuned_config


def _shapes():
    if os.environ.get("REPRO_BENCH_SMOKE"):
        return dict(b=256, p=32, d=1024, k=128, repeat=2)
    return dict(b=512, p=64, d=2048, k=256, repeat=3)


def _corpus(b: int, p: int, d: int, k: int, seed: int = 0):
    """Zipf-skewed synthetic tuples in df-rank order: high-df terms at the
    HIGH ids (ascending-df layout), so the occupancy/head machinery sees
    the skew it was built for."""
    rng = np.random.default_rng(seed)
    # Zipf ranks over [1, d]; rank 1 = most frequent → highest df-rank id.
    ranks = np.minimum(rng.zipf(1.3, size=(b, p)), d)
    ids = np.sort((d - ranks).astype(np.int32), axis=1)
    vals = rng.random((b, p)).astype(np.float32)
    nnz = rng.integers(p // 2, p + 1, b)
    for i in range(b):
        vals[i, nnz[i]:] = 0.0
    means_t = np.where(rng.random((d, k)) < 0.15,
                       rng.random((d, k)), 0.0).astype(np.float32)
    assign = rng.integers(0, k, b).astype(np.int32)
    return (jnp.asarray(ids), jnp.asarray(vals), jnp.asarray(means_t),
            jnp.asarray(assign))


def _timed(fn, repeat):
    def call():
        return jax.block_until_ready(fn())

    return time_call_warm(call, repeat=repeat)


def run():
    cfg = _shapes()
    b, p, d, k, repeat = cfg["b"], cfg["p"], cfg["d"], cfg["k"], cfg["repeat"]
    ids, vals, means_t, assign = _corpus(b, p, d, k)
    t_th = jnp.asarray(int(0.8 * d), jnp.int32)
    v_th = jnp.asarray(0.1, jnp.float32)
    shape_meta = {"B": b, "P": p, "D": d, "K": k}

    interpret = ops.default_interpret()
    compiled = not interpret
    mode = "compiled" if compiled else "interpret"

    # Roofline-pruned autotune at the suite's own regime, once per engine
    # (budget shrinks under REPRO_BENCH_SMOKE with the shapes).  The engines
    # search disjoint candidate spaces and cache regimes (tune/config.py).
    budget = SearchBudget.default()
    t0 = time.perf_counter()
    tuned, stats = search_tuned_config(ids, vals, dim=d, k=k, budget=budget)
    search_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    xtuned, xstats = search_tuned_config(ids, vals, dim=d, k=k,
                                         budget=budget, engine="xla_blocked")
    xsearch_s = time.perf_counter() - t0

    # Plan construction is host-side, once-per-fit work; time it in its own
    # rows so it never pollutes (nor hides inside) the per-call kernel rows.
    plan, plan_s = time_call(
        lambda: prepare_plan(ids, vals, dim=d), repeat=repeat)
    tplan, tplan_s = time_call(
        lambda: prepare_plan(ids, vals, dim=d, tuned=tuned), repeat=repeat)
    xtplan, xtplan_s = time_call(
        lambda: prepare_plan(ids, vals, dim=d, tuned=xtuned), repeat=repeat)

    def variants(ref_fn, pal, xla):
        return (
            ("reference", "reference", ref_fn, None),
            ("pallas", "pallas",
             lambda: pal(plan=None, tuned=None), False),
            ("pallas_planned", "pallas",
             lambda: pal(plan=plan, tuned=None), False),
            ("pallas_tuned", "pallas",
             lambda: pal(plan=tplan, tuned=tuned), True),
            ("xla_blocked", "xla_blocked",
             lambda: xla(plan=None, tuned=None), False),
            ("xla_blocked_planned", "xla_blocked",
             lambda: xla(plan=plan, tuned=None), False),
            ("xla_blocked_tuned", "xla_blocked",
             lambda: xla(plan=xtplan, tuned=xtuned), True),
        )

    cases = {
        "sparse_sim": variants(
            lambda: ref.sparse_sim(ids, vals, means_t),
            lambda **kw: ops.sparse_sim(ids, vals, means_t,
                                        interpret=interpret, **kw),
            lambda **kw: xb.sparse_sim(ids, vals, means_t, **kw)),
        "esicp_gather": variants(
            lambda: ref.esicp_gather(ids, vals, means_t, t_th, v_th),
            lambda **kw: ops.esicp_gather(ids, vals, means_t, t_th, v_th,
                                          interpret=interpret, **kw),
            lambda **kw: xb.esicp_gather(ids, vals, means_t, t_th, v_th,
                                         **kw)),
        "segment_update": variants(
            lambda: ref.segment_update(assign, ids, vals, k, d),
            lambda **kw: ops.segment_update(assign, ids, vals, k=k, d=d,
                                            interpret=interpret, **kw),
            lambda **kw: xb.segment_update(assign, ids, vals, k=k, d=d,
                                           **kw)),
        "rho_gather": variants(
            lambda: ref.rho_gather(assign, ids, vals, means_t),
            lambda **kw: ops.rho_gather(assign, ids, vals, means_t,
                                        interpret=interpret, **kw),
            lambda **kw: xb.rho_gather(assign, ids, vals, means_t, **kw)),
    }

    rows = []
    for name, var in cases.items():
        ref_best = None
        default_best = {}                    # engine -> its default's best
        for suffix, backend, fn, is_tuned in var:
            if suffix == "reference":
                _, ref_best, warm = _timed(jax.jit(fn), repeat)
                rows.append(bench_row(f"kernel_suite/{name}_reference",
                                      ref_best * 1e6, "reference",
                                      warmup_us=warm * 1e6, **shape_meta))
                continue
            _, best, warm = _timed(fn, repeat)
            is_xla = backend == "xla_blocked"
            extra = dict(shape_meta)
            # xla_blocked always compiles — same execution mode as the
            # reference on every platform, so the cross-engine ratio is a
            # kernel measurement everywhere; pallas rows are only
            # comparable when the kernels actually compiled.
            extra.update(interpret=False if is_xla else interpret,
                         mode="xla" if is_xla else mode, tuned=is_tuned)
            extra.update(speedup_fields(ref_best, best,
                                        comparable=is_xla or compiled))
            if suffix in ("pallas_planned", "xla_blocked"):
                # Each engine's tuned row is judged against that engine's
                # default configuration: planned default geometry for
                # pallas, the plan-less gather for xla_blocked.
                default_best[backend] = best
            if is_tuned and backend in default_best:
                # Same engine, same mode, tuned vs default — valid on every
                # platform, including interpret-only ones.
                extra["speedup_vs_default"] = round(
                    default_best[backend] / best, 4)
            rows.append(bench_row(f"kernel_suite/{name}_{suffix}",
                                  best * 1e6, backend,
                                  warmup_us=warm * 1e6, **extra))

    for pname, pbackend, secs in (
            ("plan_build_default", "pallas", plan_s),
            ("plan_build_tuned", "pallas", tplan_s),
            ("plan_build_xla_tuned", "xla_blocked", xtplan_s)):
        rows.append(bench_row(
            f"kernel_suite/{pname}", secs * 1e6, pbackend,
            interpret=False, mode="host", comparable=False, speedup=None,
            **shape_meta))

    rows.append(bench_row(
        "kernel_suite/autotuner", search_s * 1e6, "pallas",
        interpret=interpret, mode=mode, tuned=True,
        comparable=False, speedup=None,
        winner=tuned.to_dict(), **stats.to_dict(), **shape_meta))
    rows.append(bench_row(
        "kernel_suite/autotuner_xla", xsearch_s * 1e6, "xla_blocked",
        interpret=False, mode="xla", tuned=True,
        comparable=False, speedup=None,
        winner=xtuned.to_dict(), **xstats.to_dict(), **shape_meta))
    return rows

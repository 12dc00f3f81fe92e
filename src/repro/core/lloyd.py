"""Lloyd-iteration driver for accelerated spherical K-means.

Runs assignment (selected algorithm × backend) → update → [EstParams at
iterations 1–2] until no assignment changes, collecting the paper's
diagnostics per iteration: Mult (multiply-adds), CPR (complementary pruning
rate, Eq. 22), #changed, objective J (Eq. 47).  All algorithms converge to
the identical fixed point from the same seed — the acceleration contract.

Host-sync discipline (DESIGN.md §8): the fit is an *unrolled prologue*
covering the EstParams iterations (estimating (t_th, v_th) needs host-side
grid bookkeeping) followed by ONE jitted, buffer-donated call that runs the
rest of the fit as a ``lax.while_loop`` on device — assignment epoch →
update → ρ_self refresh → convergence test per trip, with every diagnostic
written into a per-iteration ring buffer carried through the loop.  The
host pulls diagnostics once per prologue iteration (≤ 2) and once for the
whole fused remainder: O(1) syncs per *fit*, independent of n_iter.

Each assignment epoch is a ``lax.map`` over reshaped batches: documents are
padded to a batch-size multiple with dead rows (nnz = 0, ρ_self = 0) that
are masked out of every diagnostic; the tail batch therefore runs through
the identical code path as full batches (tested in tests/test_backends.py).
On the reference engine the batches are tiles of documents of like length
(:class:`TileOrder`), each scanned only to its longest document.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.sparse import SparseDocs, pad_rows
from repro.core.backends import live_steps, resolve_backend, scan_fold
from repro.core.meanindex import (StructuralParams, build_mean_index,
                                  index_from_means_t, normalized_means)
from repro.core.assignment import assign_batch
from repro.core.update import (KMeansState, drift_loosen, group_drift,
                               init_state, init_state_from_store,
                               n_ub_groups, moving_flags, update_step)
from repro.core.estparams import estimate_params, EstGrid

# Single host-sync points — module-level so tests can wrap them and count
# device→host transfers.
_host_pull = jax.device_get


def _pull(x):
    with obs.span("lloyd.pull"):
        return _host_pull(x)


def _plan_tiles(plan, nb: int, bs: int):
    """A tiled :class:`~repro.kernels.plan.KernelPlan`'s leaves reshaped for
    a (nb, bs)-tile ``lax.scan`` — the per-tile xs the epoch scans beside
    the data tiles.  None plan (reference backend) → None."""
    if plan is None:
        return None
    resh2 = lambda a: None if a is None else a.reshape((nb, -1) + a.shape[1:])
    return (resh2(plan.occ), resh2(plan.head), resh2(plan.headc))


def _tile_plan(plan, xs_plan):
    """Rebuild the per-tile plan from a scan step's sliced leaves."""
    if plan is None or xs_plan is None:
        return None
    occ, head, headc = xs_plan
    return dataclasses.replace(plan, occ=occ, head=head, headc=headc)


def _update_plan(plan, bs: int):
    """The plan as the full-array update phase may consume it: the cached
    head slabs always apply, but a per-``bs``-tile occupancy grouping only
    coincides with the flat call's ``b_blk`` grouping when the tile size is
    a ``b_blk`` multiple — otherwise drop occ (recomputed inline)."""
    if plan is None:
        return None
    return plan if bs % plan.b_blk == 0 else plan.without_occ()


class TileOrder(NamedTuple):
    """The padded corpus with its rows ordered by length, for the epoch's
    tiles: each tile then holds documents of like length, and the
    reference scan runs each only to its longest document.  Documents are
    constant across iterations, so it is built once per fit."""

    docs: SparseDocs     # rows in length order
    perm: jax.Array      # (N,) int32: ordered row -> original row
    inv: jax.Array       # (N,) int32: original row -> ordered row


@jax.jit
def length_order(docs: SparseDocs) -> TileOrder:
    """Rows by ``nnz``, stably: rows of equal length keep their order."""
    n = docs.n_docs
    perm = jnp.argsort(docs.nnz, stable=True).astype(jnp.int32)
    inv = jnp.zeros((n,), jnp.int32).at[perm].set(
        jnp.arange(n, dtype=jnp.int32))
    return TileOrder(docs=SparseDocs(ids=docs.ids[perm],
                                     vals=docs.vals[perm],
                                     nnz=docs.nnz[perm], dim=docs.dim),
                     perm=perm, inv=inv)


@partial(jax.jit, static_argnames=("p", "bs", "fold"))
def scan_slot_share(nnz: jax.Array, p: int, bs: int, fold: int) -> jax.Array:
    """Slots one assignment epoch gathers over ``bs``-row tiles of rows of
    these ``nnz`` (in the order the epoch scans them), ``fold`` slots per
    step, as a share of the tiles × ``p`` slots of a scan over every slot:
    each tile's steps run to its longest row rounded up to a whole fold."""
    nb = nnz.shape[0] // bs
    steps = jnp.sum(jax.vmap(lambda t: live_steps(t, p, fold))(
        nnz.reshape(nb, bs)))
    return steps * fold / (nb * p)


@partial(jax.jit, static_argnames=("algo", "backend", "bs"))
def _fused_epoch(algo: str, backend: str, docs: SparseDocs, index,
                 assign, rho_self, xstate, valid, bs: int, plan=None,
                 ub=None, order: TileOrder | None = None):
    """One full assignment epoch over a resident slab, on device.

    A chunk-scan: ``lax.scan`` over ``bs``-row tiles whose *carry* is the
    scalar diagnostic accumulators (Mult, |Z| sum, #changed) and whose
    stacked output is the per-tile assignment + refreshed per-object bound —
    no per-batch host syncs, and no (nb,)-shaped diagnostic intermediates to
    reduce afterwards.  The same scan body serves every tile (uniform
    shapes), which is what lets the streaming fit reuse this function per
    DocStore chunk.  (Per-object ρ is not returned: the update step
    refreshes ρ_self against the *new* means anyway.)

    ``plan`` is the backend's prepared epoch-invariant cache built with
    ``tile_rows=bs`` (``Backend.prepare``); its occupancy/head-slab arrays
    ride the scan as per-tile xs beside the data tiles.  ``ub`` is the
    maintained per-object bound (bounds modes; None → +inf 'unknown').

    ``order`` (:class:`TileOrder` of ``docs``) makes the tiles those of its
    length-ordered rows: the per-document inputs are taken through its
    ``perm`` and the results put back through its ``inv``, so the outputs
    stay in ``docs``' row order.  Each document's own sums are unchanged;
    only the float ``mult`` is added up over the tiles in another order.
    """
    n = docs.ids.shape[0]
    nb = n // bs
    if ub is None:
        ub = jnp.full((n, n_ub_groups(index.k)), jnp.inf, jnp.float32)
    if order is not None:
        docs = order.docs
        assign, rho_self, xstate, valid, ub = (
            a[order.perm] for a in (assign, rho_self, xstate, valid, ub))
    resh = lambda a: a.reshape((nb, bs) + a.shape[1:])

    def tile_fn(carry, xs):
        (bids, bvals, bnnz, bassign, brho, bxs, bvalid, bub), xs_plan = xs
        bdocs = SparseDocs(ids=bids, vals=bvals, nnz=bnnz, dim=docs.dim)
        res = assign_batch(algo, backend, bdocs, index, bassign, brho, bxs,
                           _tile_plan(plan, xs_plan), bub)
        mult, cand, changed = carry
        carry = (mult + res.mult,
                 cand + jnp.sum(jnp.where(bvalid, res.n_candidates, 0)),
                 changed + jnp.sum(res.changed & bvalid))
        return carry, (res.assign, res.ub)

    carry0 = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
              jnp.zeros((), jnp.int32))
    (mult, cand, changed), (a, u) = lax.scan(
        tile_fn, carry0,
        ((resh(docs.ids), resh(docs.vals), resh(docs.nnz),
          resh(assign), resh(rho_self), resh(xstate), resh(valid),
          resh(ub)),
         _plan_tiles(plan, nb, bs)))
    a, u = a.reshape(n), u.reshape((n,) + u.shape[2:])
    if order is not None:
        a, u = a[order.inv], u[order.inv]
    return a, u, mult, cand, changed


def _device_iteration(algo, backend, docs, state, valid, *, bs, k,
                      plan=None, order=None):
    """One full Lloyd iteration (epoch + update), traceable on device.

    Returns (state', (mult, cand_sum, n_changed, objective)).  Shared by the
    host-stepped prologue and the fused while_loop body, so both paths run
    the identical computation graph.  Its operations carry their phase in
    their op names: ``assign``, ``update.*`` (``update_step``) and
    ``lloyd.diag``.
    """
    prev_assign = state.assign
    with jax.named_scope("assign"):
        assign, ub, mult, cand_sum, n_changed = _fused_epoch(
            algo, backend, docs, state.index, state.assign, state.rho_self,
            state.xstate, valid, bs, plan, state.ub, order)
    state = update_step(docs, assign, prev_assign, state,
                        state.index.params, k=k, backend=backend,
                        plan=_update_plan(plan, bs), ub=ub)
    with jax.named_scope("lloyd.diag"):
        objective = jnp.sum(jnp.where(valid, state.rho_self, 0.0))
    return state, (mult, cand_sum, n_changed, objective)


def _fused_fit_body(state, docs, valid, last_changed, plan, order=None, *,
                    algo, backend, bs, k, max_steps):
    """The fused remainder of the fit: a ``lax.while_loop`` over iterations.

    Carries (state, step counter, #changed of the previous iteration, ring
    buffer).  The ring buffer holds one slot per potential iteration for
    every diagnostic; slots past the executed step count stay zero and are
    discarded on the host.  Entering with ``last_changed == 0`` (the
    prologue already converged) runs zero trips.
    """
    zf = jnp.zeros((max_steps,), jnp.float32)
    zi = jnp.zeros((max_steps,), jnp.int32)
    ring = {"mult": zf, "cand": zf, "changed": zi, "objective": zf,
            "n_moving": zi, "t_th": zi, "v_th": zf}

    def cond(carry):
        _, it, changed, _ = carry
        return (it < max_steps) & (changed != 0)

    def body(carry):
        state, it, _, ring = carry
        state, (mult, cand, changed, obj) = _device_iteration(
            algo, backend, docs, state, valid, bs=bs, k=k, plan=plan,
            order=order)
        changed = changed.astype(jnp.int32)
        with jax.named_scope("lloyd.diag"):
            ring = {
                "mult": ring["mult"].at[it].set(mult),
                "cand": ring["cand"].at[it].set(cand.astype(jnp.float32)),
                "changed": ring["changed"].at[it].set(changed),
                "objective": ring["objective"].at[it].set(obj),
                "n_moving": ring["n_moving"].at[it].set(state.index.n_moving),
                "t_th": ring["t_th"].at[it].set(state.index.params.t_th),
                "v_th": ring["v_th"].at[it].set(state.index.params.v_th),
            }
        return (state, it + 1, changed, ring)

    state, n_steps, _, ring = lax.while_loop(
        cond, body,
        (state, jnp.asarray(0, jnp.int32), last_changed, ring))
    return state, n_steps, ring


@functools.lru_cache(maxsize=None)
def _fused_fit_fn(algo: str, backend: str, bs: int, k: int, max_steps: int):
    """Jitted fused-fit entry, donated state buffers (donation is a no-op on
    CPU, where XLA has no aliasing support — skipped to avoid the warning).
    Its executable is named ``jit_lloyd_fused_fit``."""
    donate = (0,) if jax.default_backend() != "cpu" else ()

    def lloyd_fused_fit(state, docs, valid, last_changed, plan, order=None):
        return _fused_fit_body(state, docs, valid, last_changed, plan, order,
                               algo=algo, backend=backend, bs=bs, k=k,
                               max_steps=max_steps)

    return jax.jit(lloyd_fused_fit, donate_argnums=donate)


def _run_fused(algo, backend, bs, k, max_steps, state, docs, valid,
               last_changed, plan=None, order=None):
    """Indirection point for tests asserting the fused path is one call."""
    fn = _fused_fit_fn(algo, backend, bs, k, max_steps)
    return fn(state, docs, valid, last_changed, plan, order)


@dataclasses.dataclass
class LloydResult:
    state: KMeansState
    assign: np.ndarray
    history: list
    params: StructuralParams
    converged: bool
    n_iter: int
    # Streaming fits only: (next_epoch, next_chunk) where a resumed fit
    # would continue — None for converged / resident fits.
    cursor: tuple | None = None
    # The autotuned kernel config the fit's plans were built with
    # (repro.tune.TunedConfig), or None when tuning was off / missed.
    # Rides into FittedModel so save/load round-trips the winner.
    tuned: object | None = None

    @property
    def objective(self) -> float:
        """J = Σ_i x_i·μ_{a(i)} (Eq. 47) at the final state."""
        return float(jnp.sum(self.state.rho_self))


def initial_params(spec, dim: int) -> StructuralParams:
    """'auto' / None / StructuralParams -> the fit's starting thresholds.

    'auto' and None start trivial: t_th=0, v_th=1 puts everything in
    Region 3 under a vacuous bound, i.e. iteration 1 behaves like the
    unfiltered baseline — exactly the paper (EstParams runs at r=1,2).
    """
    if isinstance(spec, StructuralParams):
        return spec
    return StructuralParams.trivial(dim)


def _history_row(r: int, n: int, k: int, mult, cand, changed, obj, nmov,
                 t_th, v_th, elapsed: float, counts: dict | None = None
                 ) -> dict:
    return {
        "iteration": r,
        "mult": float(mult),
        "cpr": float(cand) / (n * k),
        "n_changed": int(changed),
        "objective": float(obj),
        "n_moving": int(nmov),
        "elapsed_s": elapsed,
        "t_th": int(t_th),
        "v_th": float(v_th),
        **(counts or {}),
    }


def lloyd_fit(docs: SparseDocs, *, k: int, algo: str = "esicp",
              backend: str = "reference", params="auto",
              batch_size: int = 4096, max_iter: int = 60,
              est_grid: EstGrid | None = None, est_iters=(1, 2),
              seed: int = 0, df: jax.Array | None = None,
              tune: str = "off", tune_budget=None) -> LloydResult:
    """Single-host Lloyd fit: the paper's pipeline as one function.

    algo: 'mivi' | 'icp' | 'es' | 'esicp' | 'ta-icp' | 'cs-icp'
    backend: 'reference' | 'pallas' | 'xla_blocked' | 'auto' — accumulator
            engine for the assignment AND update steps (core/backends.py;
            'auto' = pallas on TPU, the compiled xla_blocked twins
            elsewhere).
    params: 'auto' (EstParams at iterations 1–2, the paper's default),
            StructuralParams for fixed thresholds, or None -> trivial.
    tune: 'off' | 'cached' | 'search' — kernel-engine autotuning
            (``Backend.prepare``; no-op on the reference backend).
            ``tune_budget`` is a :class:`repro.tune.SearchBudget` (or int
            max-timed-candidates) for 'search' mode.

    This is the ``single_host`` execution strategy behind the
    :class:`repro.cluster.SphericalKMeans` estimator; call the estimator for
    the artifact-producing front door, this for the raw :class:`LloydResult`.

    Traced in ``repro.lloyd.*`` spans (``repro.obs``): ``prepare``, one
    ``iteration`` per prologue iteration, ``fused``, ``pull`` around every
    device→host sync and ``finish``.  Each history row also holds its share
    of the fit's counters (:class:`repro.obs.RowCounts`): the first row
    holds the set-up's, the first fused row the whole fused call's (0 on
    the later ones) and the last row the finish's.  ``scan_fold`` and
    ``scan_slot_share`` are the same on every row: the reference scan's fold
    for the epoch's tile width (:func:`~repro.core.backends.scan_fold`) and
    :func:`scan_slot_share` of the epoch's tiles at that fold (engines with
    a plan run no slot scan; there they read what the reference scan would
    take over their tiles).
    """
    with obs.fit() as rec:
        counts = obs.RowCounts(rec)
        est_grid = est_grid or EstGrid()
        est_iters = tuple(est_iters)
        n = docs.n_docs
        with obs.span("lloyd.prepare"):
            init_params = initial_params(params, docs.dim)
            # Seeding picks centroids among the *real* documents, before
            # padding.
            state = init_state(docs, k, init_params, seed=seed)
            if df is None:
                df = docs.df        # cached on the corpus (sparse/matrix.py)

            bs = min(batch_size, n)
            pdocs = pad_rows(docs, bs)
            n_pad = pdocs.n_docs
            valid = jnp.arange(n_pad) < n
            # Epoch-invariant kernel plan (occupancy + cached high-df head
            # slabs): documents never change across Lloyd iterations, so the
            # pallas backend densifies the head region and maps the live
            # cells exactly once per fit; the reference backend has nothing
            # to cache (None).
            plan = resolve_backend(backend).prepare(pdocs, tile_rows=bs, k=k,
                                                    tune=tune,
                                                    tune_budget=tune_budget)
            # The reference engine (no plan) scans each tile only to its
            # longest document, so its tiles take documents of like length.
            # A plan is laid out in the corpus's row order and is handed to
            # the update as well, so engines with one keep that order.
            order = length_order(pdocs) if plan is None else None
            fold = scan_fold(pdocs.pad_width)
            slot_share = scan_slot_share(
                (pdocs if order is None else order.docs).nnz,
                p=pdocs.pad_width, bs=bs, fold=fold)
            if n_pad != n:
                pad = n_pad - n
                # Dead rows carry ρ_self = 0 — exactly the value every update
                # step recomputes for them (no live tuples ⇒ zero similarity)
                # — and the objective reduction masks on `valid` regardless,
                # so padding never leaks into the history.
                state = dataclasses.replace(
                    state,
                    assign=jnp.pad(state.assign, (0, pad)),
                    rho_self=jnp.pad(state.rho_self, (0, pad)),
                    rho_self_prev=jnp.pad(state.rho_self_prev, (0, pad)),
                    # Dead rows pad ub = 0 (the ρ_self convention's twin):
                    # their bound may drift upward across updates, which is
                    # harmless — dead rows have no live tuples, so they
                    # contribute zero Mult and are valid-masked out of |Z| /
                    # #changed.
                    ub=jnp.pad(state.ub, ((0, pad), (0, 0))),
                )

        history = []
        converged = False

        # --- Prologue: the EstParams iterations, host-stepped ---------
        # estimate_params needs host-side grid bookkeeping (dynamic-shape
        # candidate grids), so iterations 1..max(est_iters) run outside the
        # fused loop: still fully on device per step, with one diagnostic
        # pull each — a constant ≤ max(est_iters) syncs.
        prologue = 0
        if params == "auto" and est_iters:
            prologue = min(max(est_iters), max_iter)
        for r in range(1, prologue + 1):
            with obs.span("lloyd.iteration", iteration=r):
                t0 = time.perf_counter()
                state, (mult, cand_sum, n_changed, _) = _device_iteration(
                    algo, backend, pdocs, state, valid, bs=bs, k=k,
                    plan=plan, order=order)
                if r in est_iters:
                    # EstParams' first host sync waits for this iteration
                    # on the device; waiting here instead keeps that wait
                    # out of its span.
                    jax.block_until_ready(state)
                    # EstParams sees only the real rows (padding would skew
                    # the Mult-estimate tables).
                    new_params, _ = estimate_params(
                        docs, df, state.index.means_t, state.rho_self[:n],
                        k=k, grid=est_grid)
                    state = dataclasses.replace(
                        state, index=state.index.with_params(new_params))
                diag = _pull(
                    (mult, cand_sum, n_changed,
                     jnp.sum(jnp.where(valid, state.rho_self, 0.0)),
                     state.index.n_moving, state.index.params.t_th,
                     state.index.params.v_th, slot_share))
                history.append(_history_row(
                    r, n, k, *diag[:-1], time.perf_counter() - t0,
                    {"scan_slot_share": float(diag[-1]), "scan_fold": fold,
                     **counts.take()}))
            if history[-1]["n_changed"] == 0:
                converged = True
                break

        # --- Fused remainder: one jitted call, O(1) host syncs --------
        max_steps = max_iter - len(history)
        if not converged and max_steps > 0:
            last_changed = jnp.asarray(
                history[-1]["n_changed"] if history else 1, jnp.int32)
            with obs.span("lloyd.fused"):
                t0 = time.perf_counter()
                state, n_steps, ring = _run_fused(
                    algo, backend, bs, k, max_steps,
                    state, pdocs, valid, last_changed, plan, order)
                # The one device→host sync of the fused remainder: the
                # executed step count and every diagnostic ring cross in a
                # single pull.
                steps, ring_h, share = _pull((n_steps, ring, slot_share))
                steps = int(steps)
                per_iter = (time.perf_counter() - t0) / max(steps, 1)
            fused_counts = counts.take()
            for i in range(steps):
                history.append(_history_row(
                    len(history) + 1, n, k, ring_h["mult"][i],
                    ring_h["cand"][i], ring_h["changed"][i],
                    ring_h["objective"][i], ring_h["n_moving"][i],
                    ring_h["t_th"][i], ring_h["v_th"][i], per_iter,
                    {"scan_slot_share": float(share), "scan_fold": fold,
                     **(fused_counts if i == 0
                        else dict.fromkeys(fused_counts, 0))}))
            converged = steps > 0 and int(ring_h["changed"][steps - 1]) == 0

        with obs.span("lloyd.finish"):
            if n_pad != n:
                # Trim the padding rows so state arrays pair with the
                # caller's docs again (dead rows carry ρ_self = 0, so
                # Σ ρ_self — the objective — is identical before and after
                # the trim).
                state = dataclasses.replace(
                    state,
                    assign=state.assign[:n],
                    rho_self=state.rho_self[:n],
                    rho_self_prev=state.rho_self_prev[:n],
                    ub=state.ub[:n],
                )
            assign = np.asarray(state.assign)
        if history:
            last = history[-1]
            for key, value in counts.take().items():
                last[key] += value
    return LloydResult(
        state=state,
        assign=assign,
        history=history,
        params=state.index.params,
        converged=converged,
        n_iter=len(history),
        tuned=None if plan is None else plan.tuned,
    )


# ---------------------------------------------------------------------------
# Streaming (out-of-core) fit over a DocStore — DESIGN.md §10.
#
# The corpus never becomes one resident (N, P) array: each epoch is a
# chunk-scan over the store's uniform (C, P) chunks, fed by the async
# double-buffered prefetcher.  Only the small per-document state (assign,
# ρ_self, ρ_prev — one scalar each) and the (K, D) accumulators stay on
# device.  Host-sync discipline: every per-chunk call is an async dispatch;
# the ONE `_host_pull` per epoch reads the epoch diagnostics + convergence
# flag (O(1) syncs per epoch — the streaming analogue of §8's O(1) per fit,
# and the floor once the host must feed chunks).
# ---------------------------------------------------------------------------

# v2 added the per-object bound state (ub / ub_work) for the bounds algo
# modes; v1 checkpoints are rejected loudly by the format check below.
STREAM_CKPT_FORMAT = "repro.cluster/stream-ckpt-v2"

# Host-memory ceiling for cached per-chunk kernel plans (occupancy + head
# slabs).  Chunks over budget are re-prepared each epoch instead of cached —
# a compute/memory trade, never a correctness one.
STREAM_PLAN_CACHE_BYTES = 512 << 20


class _ChunkPlanCache:
    """Once-per-chunk-per-fit kernel plans for the streaming fit.

    Epoch 1 builds each chunk's :class:`~repro.kernels.plan.KernelPlan`
    (occupancy + densified head slabs) on the prefetcher's producer thread
    and parks a host copy; later epochs ``device_put`` the cached copy so
    the prepared slabs ride H2D beside the raw chunk instead of being
    re-densified.  A byte budget bounds host residency: chunks past it are
    simply re-prepared every epoch.  ``None`` plans (reference backend:
    nothing to cache) cost nothing and short-circuit.
    """

    def __init__(self, backend, tile_rows: int,
                 max_bytes: int = STREAM_PLAN_CACHE_BYTES,
                 k: int | None = None, tune: str = "off", tune_budget=None):
        self._bk = backend
        self._tile_rows = tile_rows
        self._max_bytes = max_bytes
        self._host: dict[int, object] = {}
        self._bytes = 0
        self._k = k
        self._tune = tune
        self._tune_budget = tune_budget
        # Winning TunedConfig of the fit's chunks, surfaced on LloydResult.
        # Uniform chunks share a corpus signature, so the first chunk's
        # search is every later chunk's TUNED_CACHE hit.
        self.tuned = None

    @staticmethod
    def _nbytes(plan) -> int:
        return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(plan))

    def __call__(self, ci: int, cdocs):
        if ci in self._host:
            cached = self._host[ci]
            return None if cached is None else jax.device_put(cached)
        plan = self._bk.prepare(cdocs, tile_rows=self._tile_rows, k=self._k,
                                tune=self._tune,
                                tune_budget=self._tune_budget)
        if plan is not None and self.tuned is None:
            self.tuned = plan.tuned
        if plan is None:
            self._host[ci] = None
            return None
        size = self._nbytes(plan)
        if self._bytes + size <= self._max_bytes:
            self._host[ci] = jax.device_get(plan)
            self._bytes += size
        return plan


def _tile_bs(chunk_size: int, batch_size: int) -> int:
    """Tile size for scanning a (C, P) chunk: min(batch_size, C).  When the
    chunk is not a tile multiple, the chunk STEPS pad it with dead rows
    (ρ_self = 0 convention, valid-masked) rather than shrinking the tile —
    a prime chunk_size must not silently degrade into a per-row scan."""
    return max(min(batch_size, chunk_size), 1)


def _pad_chunk(cdocs: SparseDocs, extras: tuple, bs: int):
    """Pad a chunk (and its per-row companions) to a ``bs`` row multiple
    with dead rows; no-op when already aligned.  Static shapes only, so
    this folds into the jitted chunk step."""
    c = cdocs.ids.shape[0]
    pad = (-c) % bs
    if pad == 0:
        return cdocs, extras
    return (pad_rows(cdocs, bs),
            tuple(jnp.pad(e, ((0, pad),) + ((0, 0),) * (e.ndim - 1))
                  for e in extras))


# One jitted slice-writer shared by every per-document array update: `start`
# is traced, so all chunks of a fit share a single compiled program.
_set_slice = jax.jit(
    lambda buf, val, start: lax.dynamic_update_slice_in_dim(buf, val, start, 0))


@partial(jax.jit, static_argnames=("algo", "backend", "bs", "k"))
def _stream_chunk_step(algo: str, backend: str, cdocs: SparseDocs, index,
                       a_c, rho_c, xs_c, valid_c, ub_c, lam, mult, cand,
                       changed, *, bs: int, k: int, plan=None):
    """Full-batch streaming: one chunk's share of the epoch.

    Runs the identical chunk-scan `_fused_epoch` on the (C, P) tile and
    folds the chunk's cluster sums into the epoch λ accumulator via the
    backend (``init=`` is the chunked-caller hook on
    ``Backend.accumulate_means``).  One chunk == the whole corpus is the
    resident ``update_step`` bit for bit (parity-tested).  ``plan`` is the
    chunk's prepared kernel cache, carried H2D beside the chunk by the
    prefetcher (built once per chunk per fit)."""
    n_c = cdocs.ids.shape[0]
    cdocs, (a_c, rho_c, xs_c, valid_c, ub_c) = _pad_chunk(
        cdocs, (a_c, rho_c, xs_c, valid_c, ub_c), bs)
    a_new, ub_new, m, c, ch = _fused_epoch(algo, backend, cdocs, index, a_c,
                                           rho_c, xs_c, valid_c, bs, plan,
                                           ub_c)
    mvals = jnp.where(cdocs.row_mask(), cdocs.vals, 0.0)
    bk = resolve_backend(backend)
    lam = bk.accumulate_means(cdocs.ids, mvals, a_new, k=k, dim=cdocs.dim,
                              init=lam, plan=_update_plan(plan, bs))
    return a_new[:n_c], ub_new[:n_c], lam, mult + m, cand + c, changed + ch


@partial(jax.jit, static_argnames=("k",))
def _stream_update_index(lam, means_t_prev, assign, prev_assign, params, *,
                         k: int):
    """Epoch finalize: λ → unit means → fresh index + exact ICP flags (the
    non-chunked half of ``update_step``)."""
    means = normalized_means(lam, means_t_prev)
    return build_mean_index(means, params,
                            moving=moving_flags(assign, prev_assign, k))


@partial(jax.jit, static_argnames=("backend",))
def _stream_rho_chunk(backend: str, cdocs: SparseDocs, a_c, means_t,
                      plan=None):
    """ρ_self refresh for one chunk vs the NEW means (Alg. 6 lines 6–7) —
    row-independent, so the chunked refresh equals the resident one.  The
    chunk plan's cached head slabs apply after slicing to the unpadded
    chunk rows (occupancy is re-derived inline)."""
    bk = resolve_backend(backend)
    mvals = jnp.where(cdocs.row_mask(), cdocs.vals, 0.0)
    rplan = None if plan is None else plan.slice_rows(cdocs.ids.shape[0])
    return bk.self_sims(cdocs.ids, mvals, a_c, means_t, plan=rplan)


@partial(jax.jit, static_argnames=("backend", "bs", "k"))
def _stream_minibatch_chunk(backend: str, cdocs: SparseDocs, index, a_old,
                            valid_c, m_mean, counts, *, bs: int, k: int,
                            plan=None):
    """Sculley-style mini-batch step on one chunk.

    Exact nearest-centroid assignment (the shared classify accumulators),
    then per-center running means with per-center counts: applying the
    per-sample rule c ← (1−η)c + ηx, η = 1/N_c, over a batch telescopes to

        M_j ← (N_j·M_j + Σ_{x∈chunk, a(x)=j} x) / (N_j + n_j)

    — the batched form reuses ``Backend.accumulate_means`` for the sums.
    Centers the chunk never touched keep their running mean; the served
    index is the L2-projection of M onto the unit sphere."""
    bk = resolve_backend(backend)
    n_c = cdocs.ids.shape[0]
    cdocs, (a_old, valid_c) = _pad_chunk(cdocs, (a_old, valid_c), bs)
    c = cdocs.ids.shape[0]
    nb = c // bs
    resh = lambda a: a.reshape((nb, bs) + a.shape[1:])

    def tile(carry, xs):
        (bids, bvals, bnnz), xs_plan = xs
        bdocs = SparseDocs(ids=bids, vals=bvals, nnz=bnnz, dim=cdocs.dim)
        sims = bk.accumulate(bdocs, index, jnp.zeros((bs,), bool),
                             mode="exact", diag=False,
                             plan=_tile_plan(plan, xs_plan))["sims"]
        return carry, jnp.argmax(sims, axis=1).astype(jnp.int32)

    _, a = lax.scan(tile, 0,
                    ((resh(cdocs.ids), resh(cdocs.vals), resh(cdocs.nnz)),
                     _plan_tiles(plan, nb, bs)))
    a = a.reshape(c)
    a = jnp.where(valid_c, a, k)            # dead rows select no centroid
    changed = jnp.sum((a != a_old) & valid_c)
    mvals = jnp.where(cdocs.row_mask(), cdocs.vals, 0.0)
    sums = bk.accumulate_means(cdocs.ids, mvals, a, k=k, dim=cdocs.dim,
                               plan=_update_plan(plan, bs))
    n_j = jnp.zeros((k,), jnp.float32).at[a].add(
        jnp.where(valid_c, 1.0, 0.0))       # a == k scatters are dropped
    new_counts = counts + n_j
    upd = (counts[:, None] * m_mean + sums) \
        / jnp.maximum(new_counts[:, None], 1.0)
    m_mean = jnp.where((n_j > 0)[:, None], upd, m_mean)
    norms = jnp.sqrt(jnp.sum(m_mean**2, axis=1, keepdims=True))
    index_new = build_mean_index(m_mean / jnp.maximum(norms, 1e-12),
                                 index.params)
    return a[:n_c], changed, m_mean, new_counts, index_new


def _stream_ckpt_save(directory, *, step, state, lam, mult, cand, changed,
                      assign_work, ub_work, m_mean, counts, cursor, history,
                      algo_mode):
    from repro.checkpoint.store import save_checkpoint

    tree = {
        "assign": state.assign, "rho_self": state.rho_self,
        "rho_prev": state.rho_self_prev, "iteration": state.iteration,
        "ub": state.ub,
        "means_t": state.index.means_t, "moving": state.index.moving,
        "t_th": state.index.params.t_th, "v_th": state.index.params.v_th,
        "lam": lam, "mult": mult, "cand": cand, "changed": changed,
        "assign_work": assign_work, "ub_work": ub_work,
        "m_mean": m_mean, "counts": counts,
    }
    save_checkpoint(directory, tree, step=step,
                    extra={"format": STREAM_CKPT_FORMAT,
                           "cursor": list(cursor), "history": history,
                           "algo_mode": algo_mode})


def _stream_ckpt_restore(directory, *, n_rows, k, dim):
    from repro.checkpoint.store import load_extra, restore_checkpoint

    extra = load_extra(directory)
    if not extra or extra.get("format") != STREAM_CKPT_FORMAT:
        raise ValueError(f"{directory} holds no {STREAM_CKPT_FORMAT} "
                         f"checkpoint (found "
                         f"{extra.get('format') if extra else None!r})")
    example = {
        "assign": np.zeros((n_rows,), np.int32),
        "rho_self": np.zeros((n_rows,), np.float32),
        "rho_prev": np.zeros((n_rows,), np.float32),
        "iteration": np.asarray(0, np.int32),
        "ub": np.zeros((n_rows, n_ub_groups(k)), np.float32),
        "means_t": np.zeros((dim, k), np.float32),
        "moving": np.zeros((k,), bool),
        "t_th": np.asarray(0, np.int32),
        "v_th": np.asarray(0.0, np.float32),
        "lam": np.zeros((k, dim), np.float32),
        "mult": np.asarray(0.0, np.float32),
        "cand": np.asarray(0, np.int32),
        "changed": np.asarray(0, np.int32),
        "assign_work": np.zeros((n_rows,), np.int32),
        "ub_work": np.zeros((n_rows, n_ub_groups(k)), np.float32),
        "m_mean": np.zeros((k, dim), np.float32),
        "counts": np.zeros((k,), np.float32),
    }
    tree, _ = restore_checkpoint(directory, example)
    tree = {name: jnp.asarray(v) for name, v in tree.items()}
    params = StructuralParams(t_th=tree["t_th"].astype(jnp.int32),
                              v_th=tree["v_th"].astype(jnp.float32))
    index = index_from_means_t(tree["means_t"], params,
                               moving=tree["moving"])
    state = KMeansState(index=index, assign=tree["assign"],
                        rho_self=tree["rho_self"],
                        rho_self_prev=tree["rho_prev"],
                        iteration=tree["iteration"],
                        ub=tree["ub"])
    return (state, tree, tuple(extra["cursor"]), list(extra["history"]),
            extra.get("algo_mode", "full"))


def streaming_fit(store, *, k: int, algo: str = "esicp",
                  backend: str = "reference", params="auto",
                  algo_mode: str = "full", batch_size: int = 4096,
                  max_iter: int = 60, est_grid: EstGrid | None = None,
                  est_iters=(1, 2), seed: int = 0, df=None,
                  prefetch_depth: int = 2, checkpoint_dir: str | None = None,
                  checkpoint_every: int = 0,
                  resume: bool = False, tune: str = "off",
                  tune_budget=None) -> LloydResult:
    """Lloyd over an out-of-core :class:`repro.sparse.DocStore`.

    algo_mode='full': the exact chunk-scan Lloyd epoch — assignment pass
        (per-chunk `_fused_epoch` + λ accumulation) → index rebuild → ρ_self
        refresh pass.  A one-chunk store reproduces ``lloyd_fit(docs)``
        bit for bit (labels and every history diagnostic; parity-tested).
    algo_mode='minibatch': Sculley-style streaming k-means — one pass over
        the chunks per iteration, centers updated after every chunk with
        per-center counts/learning rates.  Exact nearest-centroid
        assignment (structural pruning thresholds don't apply to centers
        that move every chunk), so ``algo``/``params``/``est_iters`` are
        ignored in this mode.

    EstParams in full mode estimates (t_th, v_th) from the FULL corpus,
    chunk-streamed (:func:`repro.core.estparams.estimate_params_store`) —
    φ̃3 was an object-chunked sum already, so out-of-core costs nothing.

    Checkpointing: with ``checkpoint_dir``, a resumable snapshot commits
    every ``checkpoint_every`` chunks *inside* the epoch (0 → epoch
    boundaries only) plus one at each epoch boundary; ``resume=True``
    restores the latest snapshot — including mid-epoch ones — and
    continues to the identical final labels (tested).
    """
    from repro.sparse.store import ChunkPrefetcher

    if algo_mode not in ("full", "minibatch"):
        raise ValueError(f"algo_mode must be 'full' or 'minibatch', "
                         f"got {algo_mode!r}")
    bk_obj = resolve_backend(backend)
    backend = bk_obj.name
    est_grid = est_grid or EstGrid()
    est_iters = tuple(est_iters)
    n, c, n_rows = store.n_docs, store.chunk_size, store.n_rows
    n_chunks = store.n_chunks
    bs = _tile_bs(c, batch_size)
    valid = jnp.arange(n_rows) < n
    # df feeds EstParams only — don't trigger DocStore.df's full corpus
    # scan for modes that never estimate (minibatch / fixed thresholds).
    need_df = algo_mode == "full" and params == "auto" and bool(est_iters)
    if df is None and need_df:
        df = store.df
    df = None if df is None else jnp.asarray(df)

    minibatch = algo_mode == "minibatch"
    zeros_lam = jnp.zeros((k, store.dim), jnp.float32)
    # Per-chunk kernel plans, built once per fit on the prefetch thread and
    # carried H2D beside the raw chunks (None throughout on the reference
    # backend — nothing to cache).
    plan_cache = _ChunkPlanCache(bk_obj, bs, k=k, tune=tune,
                                 tune_budget=tune_budget)

    if resume:
        if not checkpoint_dir:
            raise ValueError("resume=True needs checkpoint_dir")
        state, tree, (start_epoch, start_chunk), history, ckpt_mode = \
            _stream_ckpt_restore(checkpoint_dir, n_rows=n_rows, k=k,
                                 dim=store.dim)
        if ckpt_mode != algo_mode:
            # Shapes alias across modes, so a silent continue would finish
            # with wrong labels — fail loudly instead.
            raise ValueError(
                f"checkpoint under {checkpoint_dir} was written by an "
                f"algo_mode={ckpt_mode!r} fit; cannot resume it with "
                f"algo_mode={algo_mode!r}")
        lam, mult, cand, changed = (tree["lam"], tree["mult"], tree["cand"],
                                    tree["changed"])
        assign_work, m_mean, counts = (tree["assign_work"], tree["m_mean"],
                                       tree["counts"])
        ub_work = tree["ub_work"]
    else:
        init_params = initial_params(None if minibatch else params,
                                     store.dim)
        state = init_state_from_store(store, k, init_params, seed=seed)
        m_mean = state.index.means_t.T      # (K, D) running means (seeds)
        counts = jnp.zeros((k,), jnp.float32)
        lam, mult, cand, changed = (zeros_lam, jnp.zeros((), jnp.float32),
                                    jnp.zeros((), jnp.int32),
                                    jnp.zeros((), jnp.int32))
        assign_work = state.assign
        ub_work = state.ub
        history = []
        start_epoch, start_chunk = 1, 0

    def maybe_ckpt(r, next_chunk, *, force=False):
        if not checkpoint_dir:
            return
        due = force or (checkpoint_every and next_chunk
                        and next_chunk % checkpoint_every == 0)
        if not due:
            return
        _stream_ckpt_save(
            checkpoint_dir, step=(r - 1) * (n_chunks + 1) + next_chunk,
            state=state, lam=lam, mult=mult, cand=cand, changed=changed,
            assign_work=assign_work, ub_work=ub_work, m_mean=m_mean,
            counts=counts, cursor=(r, next_chunk), history=history,
            algo_mode=algo_mode)

    converged = False
    r = start_epoch - 1
    for r in range(start_epoch, max_iter + 1):
        t0 = time.perf_counter()
        first = start_chunk if r == start_epoch else 0
        # Minibatch centers evolve per chunk; on a mid-epoch resume the
        # checkpointed index (saved after every chunk step) IS the current
        # center state, so picking it up here covers both cases.
        mb_index = state.index
        if first == 0:
            lam, mult, cand, changed = (zeros_lam,
                                        jnp.zeros((), jnp.float32),
                                        jnp.zeros((), jnp.int32),
                                        jnp.zeros((), jnp.int32))
            assign_work = state.assign
            ub_work = state.ub

        xs_full = state.xstate
        # ---- pass A: assignment (+ λ / center updates), chunk-streamed ----
        order = range(first, n_chunks)
        for ci, cdocs, cplan in ChunkPrefetcher(store, depth=prefetch_depth,
                                                order=order,
                                                prepare=plan_cache):
            s = ci * c
            sl = slice(s, s + c)
            if minibatch:
                a_new, ch, m_mean, counts, mb_index = _stream_minibatch_chunk(
                    backend, cdocs, mb_index, state.assign[sl], valid[sl],
                    m_mean, counts, bs=bs, k=k, plan=cplan)
                changed = changed + ch
                cand = cand + jnp.sum(valid[sl]).astype(jnp.int32) * k
                # keep the evolving centers checkpointable: the saved
                # means_t must be the post-chunk centers
                state = dataclasses.replace(state, index=mb_index)
            else:
                a_new, ub_new, lam, mult, cand, changed = _stream_chunk_step(
                    algo, backend, cdocs, state.index, state.assign[sl],
                    state.rho_self[sl], xs_full[sl], valid[sl],
                    state.ub[sl], lam, mult, cand, changed, bs=bs, k=k,
                    plan=cplan)
                ub_work = _set_slice(ub_work, ub_new, s)
            assign_work = _set_slice(assign_work, a_new, s)
            maybe_ckpt(r, ci + 1)

        # ---- finalize: index rebuild (full) + ρ_self refresh pass ---------
        if minibatch:
            index = mb_index
        else:
            index = _stream_update_index(lam, state.index.means_t,
                                         assign_work, state.assign,
                                         state.index.params, k=k)
        rho_parts = []
        for ci, cdocs, cplan in ChunkPrefetcher(store, depth=prefetch_depth,
                                                prepare=plan_cache):
            sl = slice(ci * c, (ci + 1) * c)
            rho_parts.append(_stream_rho_chunk(backend, cdocs,
                                               assign_work[sl],
                                               index.means_t, cplan))
        rho_new = jnp.concatenate(rho_parts)
        if minibatch:
            # Minibatch never consults the bound (exact argmax assignment);
            # carry it untouched.
            ub_full = state.ub
        else:
            # Same semantics as the resident update_step: the refreshed
            # bound holds against the OLD means, so loosen each bound group
            # by its own centroids' worst angular drift this epoch.
            ub_full = drift_loosen(
                ub_work, group_drift(index.means_t,
                                     state.index.means_t))
        state = KMeansState(index=index, assign=assign_work,
                            rho_self=rho_new,
                            rho_self_prev=state.rho_self,
                            iteration=state.iteration + 1,
                            ub=ub_full)

        if not minibatch and params == "auto" and r in est_iters:
            # Full-corpus estimate, chunk-streamed (φ̃3 is an object-chunked
            # sum already); bit-for-bit the resident estimate on a
            # one-chunk store.
            from repro.core.estparams import estimate_params_store

            new_params, _ = estimate_params_store(
                store, df, state.index.means_t, state.rho_self, k=k,
                grid=est_grid)
            state = dataclasses.replace(
                state, index=state.index.with_params(new_params))

        # ---- the ONE host sync of the epoch -------------------------------
        diag = _host_pull(
            (mult, cand, changed,
             jnp.sum(jnp.where(valid, state.rho_self, 0.0)),
             state.index.n_moving, state.index.params.t_th,
             state.index.params.v_th))
        history.append(_history_row(r, n, k, *diag,
                                    time.perf_counter() - t0))
        maybe_ckpt(r + 1, 0, force=bool(checkpoint_dir))
        if history[-1]["n_changed"] == 0:
            converged = True
            break

    state = dataclasses.replace(
        state,
        assign=state.assign[:n],
        rho_self=state.rho_self[:n],
        rho_self_prev=state.rho_self_prev[:n],
        ub=state.ub[:n],
    )
    return LloydResult(
        state=state,
        assign=np.asarray(state.assign),
        history=history,
        params=state.index.params,
        converged=converged,
        n_iter=len(history),
        cursor=None if converged else (r + 1, 0),
        tuned=plan_cache.tuned,
    )


def __getattr__(name):
    # Back-compat: the estimator moved to repro.cluster.estimator (PR 3's
    # API redesign); ``from repro.core.lloyd import SphericalKMeans`` keeps
    # resolving without dragging the cluster facade into this module's
    # import graph.
    if name == "SphericalKMeans":
        from repro.cluster.estimator import SphericalKMeans
        return SphericalKMeans
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

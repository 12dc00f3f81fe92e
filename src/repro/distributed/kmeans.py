"""Distributed spherical K-means: the paper's pipeline on a pod mesh.

Layout (DESIGN.md §4):
  objects   — sharded over the object axes ("pod","data") / ("data",);
  centroids — sharded over "model": each device owns K/|model| columns of the
              transposed mean matrix (its slice of the mean-inverted index);
  thresholds (t_th, v_th, ρ_max) — replicated; the paper's "shared with all
              objects" becomes "shared across the mesh".

One fused step = assignment + update:
  1. per (object-shard × centroid-shard): ES gathering + filter on the local
     K/|model| centroids, local top-1;
  2. (max, argmin-index) all-reduce over "model" — O(B) bytes/object batch,
     never O(B·K).  This is the only assignment-phase collective;
  3. update: local cluster sums for owned centroids produced by the pluggable
     backend accumulator (core/backends.py: reference scatter | pallas
     ``segment_update`` | xla_blocked scatter-add — any registered backend
     threads through unchanged; prepared-plan operands are built for the
     pallas engine only, the others run the exact plan-less path), psum over
     object axes (compiles to reduce-scatter + all-gather), L2 normalise;
  4. ρ_self refresh via the backend's own-centroid gather where the centroid
     shard lives, psum over "model";
  5. exact invariant-centroid (ICP) flags from membership deltas.

Every accumulator — assignment scan/kernels AND update segment reductions —
comes from the shared :mod:`repro.core.backends` protocol: the shard-local
step builds a local :class:`MeanIndex` view of its centroid slice and feeds
it to the same ``Backend.accumulate`` the single-host engine uses, so this
module owns collectives and sharding, never a private TAAT re-implementation.

Object batching inside the shard keeps the (chunk × K_loc) similarity tile
VMEM/HBM-friendly; chunk size is the software-pipelining knob measured in
EXPERIMENTS.md §Perf.

The public fitting entry point is :func:`mesh_fit` — the 'mesh' execution
strategy behind ``repro.cluster.SphericalKMeans(mesh=...)``.  The historical
``dist_fit(...)`` signature survives as a deprecation shim over it.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map


def object_axes(mesh: Mesh) -> tuple[str, ...]:
    """All mesh axes except 'model' shard the object dimension."""
    return tuple(n for n in mesh.axis_names if n != "model")


class PlanMeta(NamedTuple):
    """Static geometry of the prepared-plan operands a step function was
    built for (kernels/plan.py): occ grouping + head-cache width, plus the
    autotuned kernel config (repro.tune.TunedConfig) the geometry came
    from — carried so the reconstructed per-chunk plans launch with it."""
    b_blk: int
    d_blk: int
    n_head: int
    dim: int
    tuned: object | None = None


@dataclasses.dataclass(frozen=True)
class DistKMeansState:
    """Global jax.Arrays with the shardings described above."""
    means_t: jax.Array    # (D, K)   P(None, 'model')
    assign: jax.Array     # (N,)     P(obj)
    rho_self: jax.Array   # (N,)     P(obj)
    rho_prev: jax.Array   # (N,)     P(obj)
    moving: jax.Array     # (K,)     P('model')
    iteration: jax.Array  # ()       replicated
    ub: jax.Array         # (N, G)   P(obj, None) — drift-loosened per-bound-
    #                       group similarity upper bounds (bounds modes;
    #                       +inf = no bound known).  G = n_ub_groups(k),
    #                       replicated over 'model': groups tier the GLOBAL
    #                       centroid ids (core/update.ub_group_of), so a
    #                       shard's contiguous column slice maps to
    #                       contiguous groups.


def _local_index(means_t, moving, t_th, v_th):
    """The shard's (D, K_loc) centroid slice as a MeanIndex — the view the
    shared backend accumulators consume (thresholds are replicated, so the
    region masks are identical on every shard)."""
    from repro.core.meanindex import StructuralParams, build_mean_index

    params = StructuralParams(t_th=t_th, v_th=v_th)
    return build_mean_index(means_t.T, params, moving=moving)


def _step_local(ids, vals, valid, assign, rho_self, rho_prev, ub, means_t,
                moving, t_th, v_th, iteration, *plan_args, algo: str, axes_obj,
                k: int, obj_chunk: int, lambda_dtype=jnp.float32,
                taat_unroll: bool = False, two_phase: bool = False,
                p_block: int = 1, p_tail: int = 16,
                backend: str = "reference", plan_meta=None):
    from repro.core.assignment import SKETCH_MARGIN_BETA, _region3_bound
    from repro.core.backends import BACKENDS, gather_verify_scan
    from repro.core.meanindex import normalized_means
    from repro.core.update import drift_loosen, n_ub_groups, ub_group_size
    from repro.sparse import SparseDocs

    bk = BACKENDS[backend]
    n_loc, p = ids.shape
    d, k_loc = means_t.shape
    k0 = lax.axis_index("model") * k_loc
    xstate = (rho_self >= rho_prev) & (iteration >= 2) & valid
    index_loc = _local_index(means_t, moving, t_th, v_th)
    # Bound groups tier the GLOBAL centroid ids (static geometry; k0 is
    # traced, so the local-column → group map is a traced gather index).
    gsz = ub_group_size(k)
    n_grp = n_ub_groups(k)
    gid_loc = (k0 + jnp.arange(k_loc, dtype=jnp.int32)) // gsz   # (K_loc,)
    gmat = gid_loc[:, None] == jnp.arange(n_grp, dtype=jnp.int32)[None, :]

    # ---------------- assignment, chunked over local objects ---------------
    nc = n_loc // obj_chunk

    # Prepared-plan operands (mesh_fit builds them once per fit for the
    # pallas backend): the per-obj_chunk-tile occupancy map and, when the
    # budget allows, the cached high-df head slabs — sharded over the
    # object axes exactly like ids/vals, sliced per chunk below.
    occ = head = None
    gpt = 1
    if plan_meta is not None:
        from repro.kernels.plan import KernelPlan

        gpt = -(-obj_chunk // plan_meta.b_blk)
        occ = plan_args[0]
        if plan_meta.n_head > 0:
            head = plan_args[1]

        def _chunk_plan(o, h):
            return KernelPlan(occ=o, head=h, headc=None,
                              b_blk=plan_meta.b_blk, d_blk=plan_meta.d_blk,
                              n_head=plan_meta.n_head, dim=plan_meta.dim,
                              tuned=plan_meta.tuned)
    else:
        def _chunk_plan(o, h):
            return None

    def chunk_fn(args):
        (cids, cvals, cval, cassign, crho, cxs, cub), (cocc, chead) = args
        col_ok = moving[None, :] | ~cxs[:, None]
        cnnz = jnp.sum(cvals != 0.0, axis=1)       # tf-idf: live ⇔ val > 0
        bounded = algo in ("bounds", "sketch", "bounds-esicp")
        sk = es_ub = None
        if two_phase and algo == "esicp":
            masked, surv = gather_verify_scan(
                cids, cvals, cnnz, means_t, t_th, v_th, crho, col_ok,
                unroll=taat_unroll, p_block=p_block, p_tail=p_tail)
        else:
            # The shared backend protocol on the local tile: the reference
            # TAAT scan or the pallas kernels, exactly as the single-host
            # engine runs them (core/backends.py).
            cdocs = SparseDocs(ids=cids, vals=cvals, nnz=cnnz, dim=d)
            mode = "esicp" if algo in ("esicp", "bounds-esicp") else "exact"
            out = bk.accumulate(cdocs, index_loc, cxs, mode=mode, diag=False,
                                unroll=taat_unroll, p_block=p_block,
                                plan=_chunk_plan(cocc, chead))
            sims = out["sims"]
            if bounded:
                # The compounded modes are exact by construction: sims is
                # the full exact similarity row, selection runs unmasked;
                # the gates below drive only the candidate diagnostics and
                # the bound refresh (mirrors core/assignment.py).
                ga = (cub > crho[:, None]) & cval[:, None]   # (C, G)
                pa = jnp.take(ga, gid_loc, axis=1)           # (C, K_loc)
                rho_pos = crho > 0.0
                if algo == "bounds":
                    surv = pa
                elif algo == "sketch":
                    sk = bk.sketch_sim(cdocs, index_loc,
                                       plan=_chunk_plan(cocc, chead))
                    surv = jnp.where(rho_pos[:, None],
                                     sk > crho[:, None], True)
                else:                               # bounds-esicp
                    es_ub = out["rho12"] + out["y"] * v_th
                    gate = col_ok & pa
                    crude = (es_ub > crho[:, None]) & gate
                    r3_bound, _ = _region3_bound(cdocs, index_loc)
                    ref_ub = out["rho12"] + jnp.minimum(
                        out["y"] * v_th, r3_bound)
                    checked = crude & (
                        out["rho12"] + SKETCH_MARGIN_BETA * out["y"] * v_th
                        <= crho[:, None])
                    surv = crude & jnp.where(checked,
                                             ref_ub > crho[:, None], True)
                masked = sims
            elif algo == "esicp":
                surv = ((out["rho12"] + out["y"] * v_th)
                        > crho[:, None]) & col_ok
                masked = jnp.where(surv, sims, -jnp.inf)
            elif algo == "mivi":
                surv = jnp.ones_like(col_ok)
                masked = jnp.where(surv, sims, -jnp.inf)
            elif algo == "icp":
                surv = col_ok
                masked = jnp.where(surv, sims, -jnp.inf)
            else:
                raise ValueError(algo)
        lbest = jnp.max(masked, axis=1)
        lidx = (jnp.argmax(masked, axis=1) + k0).astype(jnp.int32)
        best = lax.pmax(lbest, "model")
        cand = jnp.where(lbest >= best, lidx, k)      # lowest global id wins
        widx = lax.pmin(cand, "model").astype(jnp.int32)
        improve = (best > crho) & cval
        na = jnp.where(improve, widx, cassign)
        n_surv = jnp.sum(jnp.where(cval[:, None], surv, False),
                         dtype=jnp.float32)
        cub_new = cub
        if bounded and algo != "sketch":
            # Refresh active groups to the global per-group second-best:
            # per local column, the tightest applicable upper bound with
            # the global winner column masked out; a local per-group max
            # (one-hot over the column→group map), then pmax over 'model'
            # completes each group's max — shards owning none of a group's
            # columns contribute -inf.
            if algo == "bounds":
                b = sims
            else:
                b = jnp.where(surv, sims, jnp.inf)
                b = jnp.minimum(b, jnp.where(checked, ref_ub, jnp.inf))
                b = jnp.minimum(b, jnp.where(gate, es_ub, jnp.inf))
                b = jnp.minimum(b, jnp.where(pa & ~col_ok,
                                             crho[:, None], jnp.inf))
            gcols = k0 + jnp.arange(k_loc, dtype=jnp.int32)[None, :]
            nb = jnp.where(gcols == na[:, None], -jnp.inf, b)
            gb = jnp.max(jnp.where(gmat[None, :, :], nb[:, :, None],
                                   -jnp.inf), axis=1)         # (C, G)
            gb = lax.pmax(gb, "model")
            cub_new = jnp.where(ga, gb, cub)
        return na, n_surv, cub_new

    resh = lambda a: a.reshape((nc, obj_chunk) + a.shape[1:])
    occ_r = None if occ is None else occ.reshape((nc, gpt) + occ.shape[1:])
    head_r = None if head is None else resh(head)
    na, n_surv, nub = lax.map(chunk_fn, ((resh(ids), resh(vals), resh(valid),
                                          resh(assign), resh(rho_self),
                                          resh(xstate), resh(ub)),
                                         (occ_r, head_r)))
    assign_new = na.reshape(n_loc)
    ub_new = nub.reshape((n_loc,) + nub.shape[2:])
    n_candidates = lax.psum(jnp.sum(n_surv), axes_obj + ("model",))

    # ---------------- update: cluster sums for owned centroids -------------
    # The backend owns the segment sums (reference scatter drops the
    # out-of-range safe_a = k_loc rows; the pallas segment_update kernel
    # never materialises them) — the psum consumes the backend accumulator.
    local_a = assign_new - k0
    in_range = (local_a >= 0) & (local_a < k_loc) & valid
    safe_a = jnp.where(in_range, local_a, k_loc)

    # Cached slabs stay exact under the in_range masking: rows outside this
    # shard's centroid range carry safe_a = k_loc, whose one-hot selection
    # row is all zero — the slab value never reaches the accumulator.
    def _upd_plan(ci):
        o = None if occ is None else lax.dynamic_slice_in_dim(
            occ, ci * gpt, gpt, 0)
        h = None if head is None else lax.dynamic_slice_in_dim(
            head, ci * obj_chunk, obj_chunk, 0)
        return _chunk_plan(o, h)

    def acc_body(ci, lam):
        sl = lambda a: lax.dynamic_slice_in_dim(a, ci * obj_chunk, obj_chunk, 0)
        cvals = jnp.where(sl(in_range)[:, None], sl(vals), 0.0)
        return bk.accumulate_means(sl(ids), cvals, sl(safe_a),
                                   k=k_loc, dim=d, init=lam,
                                   plan=_upd_plan(ci))

    lam = lax.fori_loop(0, nc, acc_body, jnp.zeros((k_loc, d), jnp.float32))
    # §Perf variant: compress the cluster-sum all-reduce (the step's dominant
    # collective) to bf16 — the k-means analogue of gradient compression.
    # Not bit-exact vs Lloyd; f32 default preserves the acceleration contract.
    lam = lax.psum(lam.astype(lambda_dtype), axes_obj).astype(jnp.float32)
    means_new = normalized_means(lam, means_t)
    means_new_t = means_new.T.astype(means_t.dtype)             # (D, K_loc)

    # ---------------- ρ_self refresh (Alg. 6 lines 6–7) --------------------
    def rho_body(ci, out):
        sl = lambda a: lax.dynamic_slice_in_dim(a, ci * obj_chunk, obj_chunk, 0)
        cvals = jnp.where(sl(in_range)[:, None], sl(vals), 0.0)
        r = bk.self_sims(sl(ids), cvals, sl(safe_a), means_new_t,
                         plan=_upd_plan(ci))
        return lax.dynamic_update_slice_in_dim(out, r, ci * obj_chunk, 0)

    rho_new = lax.fori_loop(0, nc, rho_body, jnp.zeros((n_loc,), jnp.float32))
    rho_new = lax.psum(rho_new, "model")    # exactly one shard contributes

    # ---------------- exact ICP flags from membership deltas ---------------
    changed = (assign_new != assign) & valid
    old_local = jnp.where((assign - k0 >= 0) & (assign - k0 < k_loc),
                          assign - k0, k_loc)
    mv = jnp.zeros((k_loc + 1,), jnp.int32)
    mv = mv.at[safe_a].max(changed.astype(jnp.int32))
    mv = mv.at[old_local].max(changed.astype(jnp.int32))
    moving_new = lax.psum(mv[:k_loc], axes_obj) > 0

    n_changed = lax.psum(jnp.sum(changed, dtype=jnp.float32), axes_obj)
    objective = lax.psum(jnp.sum(jnp.where(valid, rho_new, 0.0)), axes_obj)

    # Bound maintenance against the means THIS step just produced: each
    # bound group's worst per-center angular drift (local columns scattered
    # into their global groups, zero for unowned groups), pmax'ed over the
    # centroid shards ('model'), loosens every object's refreshed bounds
    # (core/update.drift_loosen) — the mesh twin of update_step's
    # group_drift pass.
    dots = jnp.sum(means_new_t * means_t, axis=0)
    d_loc = jnp.arccos(jnp.clip(dots, -1.0, 1.0))             # (K_loc,)
    delta = lax.pmax(
        jnp.max(jnp.where(gmat, d_loc[:, None], 0.0), axis=0), "model")
    ub_new = drift_loosen(ub_new, delta)

    return (means_new_t, assign_new, rho_new, rho_self, ub_new, moving_new,
            n_changed, n_candidates, objective)


def make_step_fn(mesh: Mesh, *, algo: str = "esicp", k: int,
                 obj_chunk: int = 2048, lambda_dtype=jnp.float32,
                 taat_unroll: bool = False, two_phase: bool = False,
                 p_block: int = 1, p_tail: int = 16,
                 backend: str = "reference", plan_meta: PlanMeta | None = None):
    """Builds the jitted fused assignment+update step for `mesh`.

    taat_unroll: dry-run costing mode — unrolls the P-step TAAT scan so
    XLA's cost model counts every multiply (launch/dryrun.py pass B).
    backend: 'reference' (TAAT scan) | 'pallas' (kernels on the local tile)
    | 'auto' — see core/backends.py for selection semantics.
    plan_meta: when set, the step takes the prepared-plan operands (the
    per-obj_chunk occupancy map and, if ``plan_meta.n_head > 0``, the
    cached head slabs) as trailing arguments, sharded like ids/vals —
    ``mesh_fit`` builds both once per fit (see :func:`build_plan_operands`).
    """
    from repro.core.backends import resolve_backend
    backend = resolve_backend(backend).name
    if two_phase and backend != "reference":
        raise ValueError("two_phase is a reference-backend scan variant; "
                         "use backend='reference' with it")
    axes_obj = object_axes(mesh)
    po = P(axes_obj)
    specs_in = (
        P(axes_obj, None), P(axes_obj, None), po,       # ids, vals, valid
        po, po, po,                                     # assign, rho_self, rho_prev
        P(axes_obj, None),                              # ub (N, G)
        P(None, "model"), P("model"),                   # means_t, moving
        P(), P(), P(),                                  # t_th, v_th, iteration
    )
    if plan_meta is not None:
        specs_in += (P(axes_obj, None),)                # occ
        if plan_meta.n_head > 0:
            specs_in += (P(axes_obj, None),)            # head slabs
    specs_out = (
        P(None, "model"), po, po, po, P(axes_obj, None), P("model"),
        P(), P(), P(),
    )
    fn = shard_map(
        partial(_step_local, algo=algo, axes_obj=axes_obj, k=k,
                obj_chunk=obj_chunk, lambda_dtype=lambda_dtype,
                taat_unroll=taat_unroll, two_phase=two_phase,
                p_block=p_block, p_tail=p_tail, backend=backend,
                plan_meta=plan_meta),
        mesh=mesh, in_specs=specs_in, out_specs=specs_out)
    return jax.jit(fn)


def build_plan_operands(ids, vals, valid, *, dim: int, obj_chunk: int,
                        mesh: Mesh, head_bytes: int | None = None,
                        tuned=None):
    """Once-per-fit prepared-plan operands for the pallas mesh step.

    Returns ``(plan_meta, operands)``: the per-obj_chunk-tile occupancy map
    and (budget permitting) the densified high-df head slabs, device_put
    with the same object-axis sharding as ids/vals.  Dead/padding rows are
    never occupied and densify to zero, so the global padded arrays are
    used as-is.

    ``tuned`` (repro.tune.TunedConfig) supplies the block geometry and head
    budget when set — the distributed analogue of ``prepare_plan(tuned=)``;
    an explicit ``head_bytes`` still wins over the tuned budget.
    """
    from repro.kernels import plan as kplan

    b_blk = kplan.DEFAULT_B_BLK if tuned is None else tuned.b_blk
    d_blk = kplan.DEFAULT_D_BLK if tuned is None else tuned.d_blk
    if head_bytes is None and tuned is not None:
        head_bytes = tuned.head_bytes
    axes_obj = object_axes(mesh)
    sh = NamedSharding(mesh, P(axes_obj, None))
    mvals = jnp.where(valid[:, None], vals, 0.0)
    occ = kplan.occupancy_map(ids, mvals, dim=dim, b_blk=b_blk, d_blk=d_blk,
                              tile_rows=obj_chunk)
    kw = {} if head_bytes is None else {"head_bytes": head_bytes}
    n_head = kplan.pick_n_head(ids.shape[0], dim, d_blk=d_blk,
                               with_counts=False, **kw)
    head, _ = kplan.head_slabs(ids, mvals, dim=dim, d_blk=d_blk,
                               n_head=n_head, with_counts=False)
    meta = PlanMeta(b_blk=b_blk, d_blk=d_blk,
                    n_head=0 if head is None else n_head, dim=dim,
                    tuned=tuned)
    operands = (jax.device_put(occ, sh),)
    if head is not None:
        operands += (jax.device_put(head, sh),)
    return meta, operands


# ---------------------------------------------------------------------------
# Drivers.
# ---------------------------------------------------------------------------

def dist_init_state(docs, k: int, mesh: Mesh, *, n_rows: int,
                    seed: int = 0) -> DistKMeansState:
    """Seed K centroids from random documents, shard everything onto `mesh`.

    ``docs`` may be a resident SparseDocs or an out-of-core
    :class:`repro.sparse.DocStore` — seeding gathers only the K picked
    rows (the same PRNG draw and centroid construction as the single-host
    path, so runtimes agree from iteration 0).  Nothing index- or corpus-
    sized is built on one device: each 'model' shard scatters its own
    K/|model| seed rows into its (D, K_loc) slice of the means, and the
    per-document state is created directly in its sharding.  ``n_rows``
    (>= n_docs) sizes the per-document state; rows past ``n_docs`` are
    dead padding and start at the ρ_self = 0 / ub = 0 convention.
    """
    import numpy as np

    from repro.core.update import n_ub_groups, seed_centroids, seed_rows
    from repro.sparse import SparseDocs
    from repro.sparse.store import DocStore

    n_model = mesh.shape["model"]
    if k % n_model:
        raise ValueError(f"K={k} must divide over the model axis ({n_model})")
    n = docs.n_docs
    pick = np.asarray(seed_rows(n, k, seed=seed))
    if isinstance(docs, DocStore):
        sel = docs.gather_rows(pick)
    else:
        sel = SparseDocs(ids=docs.ids[pick], vals=docs.vals[pick],
                         nnz=docs.nnz[pick], dim=docs.dim)
    k_loc = k // n_model

    def seed_shard(ids, vals, nnz):
        k0 = lax.axis_index("model") * k_loc
        sl = lambda a: lax.dynamic_slice_in_dim(a, k0, k_loc, 0)
        part = SparseDocs(ids=sl(ids), vals=sl(vals), nnz=sl(nnz),
                          dim=docs.dim)
        return seed_centroids(part, k_loc).T               # (D, K_loc)

    means_t = jax.jit(shard_map(seed_shard, mesh=mesh,
                                in_specs=(P(), P(), P()),
                                out_specs=P(None, "model")))(
        *(np.asarray(a) for a in (sel.ids, sel.vals, sel.nnz)))

    axes_obj = object_axes(mesh)
    sh = lambda spec: NamedSharding(mesh, spec)
    g = n_ub_groups(k)

    def per_doc():
        real = jnp.arange(n_rows) < n
        rho = jnp.where(real, -jnp.inf, 0.0).astype(jnp.float32)
        ub = jnp.where(real, jnp.inf, 0.0).astype(jnp.float32)
        return (jnp.zeros((n_rows,), jnp.int32), rho, rho,
                jnp.broadcast_to(ub[:, None], (n_rows, g)),
                jnp.ones((k,), bool))

    assign, rho_self, rho_prev, ub, moving = jax.jit(
        per_doc, out_shardings=(sh(P(axes_obj)), sh(P(axes_obj)),
                                sh(P(axes_obj)), sh(P(axes_obj, None)),
                                sh(P("model"))))()
    return DistKMeansState(means_t=means_t, assign=assign, rho_self=rho_self,
                           rho_prev=rho_prev, moving=moving,
                           iteration=jnp.asarray(0, jnp.int32), ub=ub)


@functools.lru_cache(maxsize=None)
def _fill_rows_fn():
    """One jitted slice-writer per dtype trace: fills a sharded object
    buffer chunk by chunk, so a DocStore streams host→devices without the
    corpus ever being resident on the host as one block.  The buffer is
    DONATED — the whole point is an in-place fill of a corpus-sized array;
    without aliasing every chunk would copy the full buffer and double the
    peak (no-op on CPU, where XLA has no donation support)."""
    donate = (0,) if jax.default_backend() != "cpu" else ()
    return jax.jit(
        lambda buf, chunk, start: lax.dynamic_update_slice_in_dim(
            buf, chunk, start, 0),
        donate_argnums=donate)


def _place_store_sharded(store, mesh: Mesh, multiple: int):
    """Stream a DocStore's chunks into mesh-sharded (ids, vals, valid)
    object arrays padded to a ``multiple`` of rows (the per-host shard view
    the shard-local step consumes)."""
    from repro.sparse.store import ChunkPrefetcher

    axes_obj = object_axes(mesh)
    n, p, c = store.n_docs, store.pad_width, store.chunk_size
    pad = (-n) % multiple
    n_pad = n + pad
    sh = lambda spec: NamedSharding(mesh, spec)
    ids = jax.device_put(jnp.zeros((n_pad, p), jnp.int32),
                         sh(P(axes_obj, None)))
    vals = jax.device_put(jnp.zeros((n_pad, p), jnp.float32),
                          sh(P(axes_obj, None)))
    fill = _fill_rows_fn()
    for ci, cdocs in ChunkPrefetcher(store):
        start = ci * c
        if start >= n_pad:
            break
        m = min(c, n_pad - start)
        cid, cval = (cdocs.ids, cdocs.vals) if m == c else \
            (cdocs.ids[:m], cdocs.vals[:m])
        ids = fill(ids, cid, start)
        vals = fill(vals, cval, start)
    valid = jax.device_put(jnp.arange(n_pad) < n, sh(P(axes_obj)))
    return ids, vals, valid, pad


def dist_assignment_update(step_fn, state: DistKMeansState, ids, vals, valid,
                           t_th, v_th, plan_operands=()):
    """One fused step; returns (new_state, diag dict).  ``plan_operands``
    are the once-per-fit prepared-plan arrays a ``plan_meta``-built step
    expects (see :func:`build_plan_operands`)."""
    (means_t, assign, rho_self, rho_prev, ub, moving,
     n_changed, n_cand, objective) = step_fn(
        ids, vals, valid, state.assign, state.rho_self, state.rho_prev,
        state.ub, state.means_t, state.moving,
        jnp.asarray(t_th, jnp.int32), jnp.asarray(v_th, jnp.float32),
        state.iteration, *plan_operands)
    new = DistKMeansState(means_t=means_t, assign=assign, rho_self=rho_self,
                          rho_prev=rho_prev, moving=moving,
                          iteration=state.iteration + 1, ub=ub)
    diag = {"n_changed": n_changed, "n_candidates": n_cand,
            "objective": objective}
    return new, diag


def mesh_fit(docs, k: int, mesh: Mesh, *, algo: str = "esicp",
             backend: str = "reference", max_iter: int = 40,
             obj_chunk: int = 1024, seed: int = 0,
             est_iters=(1, 2), df=None, checkpoint_dir: str | None = None,
             checkpoint_every: int = 5, tune: str = "off", **step_kw):
    """Full distributed Lloyd loop with EstParams and optional checkpointing.

    ``docs`` may be a resident SparseDocs or an out-of-core
    :class:`repro.sparse.DocStore` whose chunks are streamed into the
    sharded object arrays (per-host shards of the data plane; see
    :func:`_place_store_sharded`).

    Returns ``(state, history, converged, params)`` — the final sharded
    :class:`DistKMeansState` (object arrays still carry the shard-multiple
    tail padding; rows ``[:docs.n_docs]`` are the real ones), the diagnostic
    history, the convergence flag, and the final StructuralParams.

    This is the 'mesh' execution strategy behind
    ``repro.cluster.SphericalKMeans(mesh=...)`` — prefer the estimator,
    which trims padding and wraps the result in a FittedModel.
    """
    import numpy as np
    from repro.cluster.config import ClusterConfig
    from repro.core.estparams import estimate_params
    from repro.core.meanindex import StructuralParams
    from repro.sparse.store import DocStore

    # Front-door validation (the same fail-fast contract as the estimator
    # and resolve_strategy): unknown algo/backend/tune, a K that doesn't
    # divide over 'model' — all rejected before any sharded work starts.
    ClusterConfig(k=k, algo=algo, backend=backend, max_iter=max_iter,
                  chunk_size=obj_chunk, mesh=mesh, est_iters=est_iters,
                  checkpoint_dir=checkpoint_dir,
                  checkpoint_every=checkpoint_every, tune=tune).validate()

    store = docs if isinstance(docs, DocStore) else None
    n = docs.n_docs
    axes_obj = object_axes(mesh)
    n_obj_shards = int(np.prod([mesh.shape[a] for a in axes_obj]))
    multiple = n_obj_shards * obj_chunk
    sh = lambda spec: NamedSharding(mesh, spec)

    if store is not None:
        # Out-of-core ingest: chunks stream host→devices into the sharded
        # object arrays — the aggregate device memory of the mesh holds the
        # corpus, the host only ever one chunk (+ the prefetched next).
        ids, vals, valid, pad = _place_store_sharded(store, mesh, multiple)
    else:
        # Pad on the host and place each shard straight onto its devices:
        # the padded corpus is never assembled on one device.
        pad = (-n) % multiple
        rows = lambda a: np.pad(np.asarray(a),
                                ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        ids = jax.device_put(rows(docs.ids), sh(P(axes_obj, None)))
        vals = jax.device_put(rows(docs.vals), sh(P(axes_obj, None)))
        valid = jax.device_put(np.arange(n + pad) < n, sh(P(axes_obj)))

    # Dead tail rows carry ρ_self = 0 and ub = 0, matching the single-host
    # padding convention (core/lloyd.py): the refresh recomputes 0 for them
    # every iteration (no live tuples ⇒ zero similarity) and the objective
    # reduction masks on `valid` regardless, so the pad value never leaks
    # into diagnostics.
    state = dist_init_state(docs, k, mesh, seed=seed, n_rows=n + pad)
    from repro.core.backends import resolve_backend

    two_phase = step_kw.pop("two_phase", False)
    if two_phase:
        if resolve_backend(backend).name != "reference":
            # Fail fast: the rebuild at r == max(est_iters) would otherwise
            # raise after iterations of completed clustering work.
            raise ValueError("two_phase is a reference-backend scan variant; "
                             "use backend='reference' with it")
    # Once-per-fit prepared-plan operands for the kernel backend: the
    # occupancy map + cached head slabs every iteration's step reuses
    # (documents are constant across Lloyd iterations).
    plan_meta, plan_ops = None, ()
    if resolve_backend(backend).name == "pallas":
        # Tuned-config resolution is cache-only here: the sharded step is
        # compiled once per fit, so the mesh path never runs the autotuner
        # itself — a prior single-host/streaming fit (or an explicit
        # ``search_tuned_config`` run) populates the process cache, and
        # 'search' degrades to a cache lookup.  Signature is probed on the
        # first chunk / the resident corpus, matching what those paths key.
        tuned = None
        if tune not in ("off", "cached", "search"):
            raise ValueError(f"tune must be 'off', 'cached' or 'search', "
                             f"got {tune!r}")
        if tune != "off":
            from repro.tune import TUNED_CACHE, corpus_signature

            probe = store.chunk(0) if store is not None else docs
            sig = corpus_signature(probe.ids, probe.vals, dim=docs.dim, k=k)
            tuned = TUNED_CACHE.get(sig)
        plan_meta, plan_ops = build_plan_operands(
            ids, vals, valid, dim=docs.dim, obj_chunk=obj_chunk, mesh=mesh,
            tuned=tuned)
    # iterations 1–2 run trivial params (t_th=0): everything is Region 3, so
    # the windowed verification can't bound ntH — run single-phase until
    # EstParams fixes t_th, then rebuild the step (paper Alg. 6 does the same
    # index restructuring at that moment).
    step_fn = make_step_fn(mesh, algo=algo, k=k, obj_chunk=obj_chunk,
                           backend=backend, plan_meta=plan_meta, **step_kw)
    params = StructuralParams.trivial(docs.dim)

    if df is None:
        df = docs.df            # cached on the corpus (sparse/matrix.py)

    history = []
    converged = False
    for r in range(1, max_iter + 1):
        state, diag = dist_assignment_update(step_fn, state, ids, vals, valid,
                                             params.t_th, params.v_th,
                                             plan_ops)
        if algo == "esicp" and r in est_iters:
            if store is not None:
                # Full-corpus estimate, chunk-streamed (the same path the
                # streaming strategy uses); ρ rows beyond the store's tail
                # are the dead-row 0 convention and contribute nothing.
                from repro.core.estparams import estimate_params_store

                rho_rows = state.rho_self[:n]
                rho_rows = jnp.pad(rho_rows, (0, store.n_rows - n))
                params, _ = estimate_params_store(
                    store, df, state.means_t, rho_rows, k=k)
            else:
                params, _ = estimate_params(docs, df, state.means_t,
                                            state.rho_self[:n], k=k)
            if two_phase and r == max(est_iters):
                if store is not None:
                    t = int(params.t_th)
                    slots = np.arange(store.pad_width)[None, :]
                    nt_h = 0
                    for j in range(store.n_chunks):
                        cid, _, cnnz = store.host_chunk(j)
                        tail = (np.asarray(cid) >= t) \
                            & (slots < np.asarray(cnnz)[:, None])
                        nt_h = max(nt_h, int(tail.sum(axis=1).max(initial=0)))
                else:
                    nt_h = int(jnp.max(jnp.sum(
                        (docs.ids >= params.t_th) & docs.row_mask(), axis=1)))
                pb = step_kw.get("p_block", 1)
                p_tail = max(nt_h + ((-nt_h) % max(pb, 1)), pb)
                step_fn = make_step_fn(mesh, algo=algo, k=k,
                                       obj_chunk=obj_chunk, two_phase=True,
                                       p_tail=p_tail, backend=backend,
                                       **step_kw)
        history.append({"iteration": r,
                        "n_changed": float(diag["n_changed"]),
                        "cpr": float(diag["n_candidates"]) / (n * k),
                        "objective": float(diag["objective"]),
                        "t_th": int(params.t_th), "v_th": float(params.v_th)})
        if checkpoint_dir and r % checkpoint_every == 0:
            from repro.checkpoint import save_checkpoint
            save_checkpoint(checkpoint_dir, {
                "means_t": state.means_t, "assign": state.assign,
                "rho_self": state.rho_self, "rho_prev": state.rho_prev,
                "moving": state.moving, "iteration": state.iteration,
                "ub": state.ub,
                "t_th": params.t_th, "v_th": params.v_th}, step=r)
        if history[-1]["n_changed"] == 0:
            converged = True
            break
    return state, history, converged, params


def dist_fit(docs, k: int, mesh: Mesh, **kw):
    """Deprecated pre-redesign entry point; use
    ``repro.cluster.SphericalKMeans(k, mesh=mesh, ...)`` (or :func:`mesh_fit`
    for the raw sharded state).  Same kwargs, same ``(state, history,
    converged)`` return value."""
    warnings.warn(
        "dist_fit is deprecated: construct repro.cluster.SphericalKMeans("
        "k, mesh=mesh, chunk_size=...) and call fit(), or use "
        "distributed.kmeans.mesh_fit for the raw sharded state.",
        DeprecationWarning, stacklevel=2)
    state, history, converged, _ = mesh_fit(docs, k, mesh, **kw)
    return state, history, converged


def make_assign_fn(mesh: Mesh, *, k: int, obj_chunk: int = 2048,
                   backend: str = "reference"):
    """Serving mode: classify new documents against a FROZEN mean index.

    The paper's engine as a lookup service — the assignment phase only
    (ES gathering + filter + (max, argmin-id) reduction over 'model'),
    no update step, no ICP state.  Returns assign (N,), sims (N,).
    """
    from repro.core.backends import resolve_backend
    backend = resolve_backend(backend).name
    axes_obj = object_axes(mesh)
    po = P(axes_obj)

    def _local(ids, vals, valid, means_t, t_th, v_th):
        from repro.core.backends import BACKENDS
        from repro.sparse import SparseDocs

        bk = BACKENDS[backend]
        n_loc, p = ids.shape
        d, k_loc = means_t.shape
        k0 = lax.axis_index("model") * k_loc
        nc = n_loc // obj_chunk
        index_loc = _local_index(means_t, jnp.ones((k_loc,), bool), t_th, v_th)

        def chunk_fn(args):
            cids, cvals, cval = args
            cdocs = SparseDocs(ids=cids, vals=cvals,
                               nnz=jnp.sum(cvals != 0.0, axis=1), dim=d)
            sims = bk.accumulate(cdocs, index_loc,
                                 jnp.zeros((obj_chunk,), bool),
                                 mode="exact", diag=False)["sims"]
            # serving has no previous similarity: bound via running best —
            # one exact pass, filter diagnostics only
            masked = jnp.where(jnp.ones_like(sims, bool), sims, -jnp.inf)
            lbest = jnp.max(masked, axis=1)
            lidx = (jnp.argmax(masked, axis=1) + k0).astype(jnp.int32)
            best = lax.pmax(lbest, "model")
            cand = jnp.where(lbest >= best, lidx, k)
            widx = lax.pmin(cand, "model").astype(jnp.int32)
            return jnp.where(cval, widx, 0), jnp.where(cval, best, 0.0)

        resh = lambda a: a.reshape((nc, obj_chunk) + a.shape[1:])
        aa, ss = lax.map(chunk_fn, (resh(ids), resh(vals), resh(valid)))
        return aa.reshape(n_loc), ss.reshape(n_loc)

    fn = shard_map(_local, mesh=mesh,
                   in_specs=(P(axes_obj, None), P(axes_obj, None), po,
                             P(None, "model"), P(), P()),
                   out_specs=(po, po))
    return jax.jit(fn)

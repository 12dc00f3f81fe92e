"""jit'd public wrappers over the Pallas kernels.

Handles block-alignment padding (to MXU-friendly multiples), dispatches to
interpret mode off-TPU, and slices results back to logical shapes.  Callers
see plain jnp-like functions; the kernels see only aligned shapes.

Kernel engine v2 plumbing (ISSUE 5):

* **K superblocks** — K is padded to ``k_blk`` and then covered by
  ``k_sup``-wide grid blocks (the whole padded K when it fits the VMEM
  budget), so the (B_blk, D_blk) densified slab is built once per (B, D)
  block instead of once per (B, K, D) step.
* **Occupancy** — every clustering-kernel call carries a
  (B-tile, D-block) live-cell map; a prepared :class:`~repro.kernels.plan.
  KernelPlan` supplies it precomputed, otherwise it is computed inline here
  (one cheap scatter-max, amortised by the densify work it prunes).
* **Prepared plans** — ``plan=`` threads the epoch-invariant cache
  (occupancy + densified high-df head slabs) from ``Backend.prepare``
  down to the kernels.  A plan whose block geometry or row layout does not
  match the call is ignored for the mismatching part: plans are an
  optimisation, never a correctness input.
* **Fused diagnostics** — ``diag=True`` on ``sparse_sim`` /
  ``esicp_gather`` returns the visited-pair counts as an extra accumulator
  of the same launch; ``with_sims=True`` on ``esicp_gather`` adds the full
  exact similarity, so one launch serves the whole ES assignment gather.
* **Tuned configs** — every structural knob (block geometry, the
  K-superblock cap) resolves through a :class:`repro.tune.config.
  TunedConfig`: explicit kwargs win, then ``tuned=``, then the config the
  prepared plan was built for (``plan.tuned``), then the hard-coded
  defaults.  The autotuner (repro/tune/search.py) searches this knob space
  per corpus regime; untouched callers get exactly the pre-tuner behaviour.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import sparse_sim as _ss
from repro.kernels import esicp_gather as _eg
from repro.kernels import esicp_filter as _ef
from repro.kernels import segment_update as _su
from repro.kernels import rho_gather as _rg
from repro.kernels import sketch_sim as _sk
from repro.kernels import flash_attention as _fa

# Widest K superblock the default auto policy will pick: bounds the
# (d_blk, k_sup) means block and the (b_blk, k_sup) accumulator blocks in
# VMEM.  TunedConfig.k_sup_cap overrides it per call.
K_SUP_CAP = 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Interpret mode exactly when no TPU backs the process: on TPU the
    kernels always run compiled, elsewhere through the Pallas interpreter
    (how the CPU tests exercise them)."""
    return not _on_tpu()


def _interpret(flag: bool | None) -> bool:
    """Resolve a wrapper's ``interpret`` argument.  ``None`` takes the
    platform default; ``False`` off-TPU is how a compile for a described
    TPU is requested.  Interpret mode is refused on a TPU: nothing may put
    the chip's kernels into the interpreter."""
    if flag is None:
        return default_interpret()
    if flag and _on_tpu():
        raise ValueError("interpret mode is not available on TPU; the "
                         "kernels run compiled there")
    return bool(flag)


# The occupancy map is a scalar-prefetch operand and lives in SMEM, which
# holds 1 MiB on v5e.  A call whose (B-tiles, D-blocks) map is larger than
# this budget — the update phase over a whole corpus — runs as a sequence
# of row chunks, each with its own slice of the map.
OCC_SMEM_BYTES = 256 << 10


def _occ_chunk_tiles(n_tiles: int, nd: int) -> int:
    """B-tiles per launch so that one launch's occupancy map fits SMEM."""
    return max(1, min(n_tiles, OCC_SMEM_BYTES // (4 * nd)))


def _row_chunks(rows, occ, b_blk: int, pad_values=None):
    """Split row operands and the occupancy map into SMEM-sized chunks.

    ``rows`` is a tuple of arrays (or None) whose leading dim is the padded
    row count.  Returns ``None`` when one launch fits, else the chunked
    ``(rows, occ)`` with a new leading chunk axis (rows padded with
    ``pad_values`` per operand, 0 by default; padded tiles are unoccupied).
    """
    nt, nd = occ.shape
    tiles = _occ_chunk_tiles(nt, nd)
    if tiles >= nt:
        return None
    nc = -(-nt // tiles)
    occ = jnp.pad(occ, ((0, nc * tiles - nt), (0, 0))).reshape(nc, tiles, nd)
    pad_values = pad_values or (0,) * len(rows)
    rows = tuple(
        None if r is None else
        _pad_to(r, nc * tiles * b_blk, 0, value=v).reshape(
            (nc, tiles * b_blk) + r.shape[1:])
        for r, v in zip(rows, pad_values))
    return rows, occ


def _map_rows(launch, rows, occ, b_blk: int):
    """``launch(rows, occ)`` for a row-independent kernel, run in SMEM-sized
    row chunks when the occupancy map needs it (outputs re-joined)."""
    chunked = _row_chunks(rows, occ, b_blk)
    if chunked is None:
        return launch(rows, occ)
    out = jax.lax.map(lambda a: launch(*a), chunked)
    return jax.tree_util.tree_map(
        lambda o: o.reshape((-1,) + o.shape[2:]), out)


def _resolve_cfg(tuned, plan, b_blk, k_blk, d_blk):
    """(TunedConfig, b_blk, k_blk, d_blk) for a call — explicit kwargs win,
    then ``tuned``, then the plan's embedded config, then defaults."""
    # Lazy: tune.config imports kernels/plan.py geometry constants, so the
    # dependency must point tune -> kernels at module-import time.
    from repro.tune.config import DEFAULT_TUNED

    cfg = tuned
    if cfg is None and plan is not None and plan.tuned is not None:
        cfg = plan.tuned
    if cfg is None:
        cfg = DEFAULT_TUNED
    return (cfg,
            cfg.b_blk if b_blk is None else b_blk,
            cfg.k_blk if k_blk is None else k_blk,
            cfg.d_blk if d_blk is None else d_blk)


def _pad_to(x, mult, axis, value=0):
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


def _align(ids, vals, means_t, b_blk, k_blk, d_blk):
    ids = _pad_to(_pad_to(ids, 8, 1), b_blk, 0)
    vals = _pad_to(_pad_to(vals, 8, 1), b_blk, 0)
    means_t = _pad_to(_pad_to(means_t, d_blk, 0), k_blk, 1)
    return ids, vals, means_t


def _pick_k_sup(kp: int, k_blk: int, k_sup: int | None,
                cap: int | None = None) -> int:
    """Exact auto K-superblock width: the *largest* multiple of ``k_blk``
    that is ≤ ``cap`` and divides padded K (the whole padded K when it fits
    the cap).

    The scan starts from ``(cap // k_blk) * k_blk`` — the true largest
    multiple — so an awkward ``cap % k_blk`` residue can never shift the
    candidate ladder off the valid widths and silently degrade the pick.
    When no multiple of ``k_blk`` in (0, cap] divides ``kp`` (``k_blk``
    wider than the cap, or a caller-supplied ``kp`` that is not ``k_blk``-
    aligned) the fallback is ``gcd(kp, k_blk)``: the widest width that is
    still guaranteed to divide ``kp``, i.e. never an invalid grid.
    """
    import math

    cap = K_SUP_CAP if cap is None else cap
    if k_sup is not None:
        assert kp % k_sup == 0, f"k_sup={k_sup} must divide padded K={kp}"
        return k_sup
    if kp <= cap:
        return kp
    for ks in range((cap // k_blk) * k_blk, 0, -k_blk):
        if kp % ks == 0:
            return ks
    return math.gcd(kp, k_blk) or k_blk


def _inline_occ(ids, vals, dp: int, d_blk: int, b_blk: int):
    """Flat-layout occupancy from the padded call operands themselves —
    the fallback when no prepared plan (or a mismatching one) is passed.
    Computed by the ONE occupancy definition (kernels/plan.py) from the
    *actual* vals operand, so callers that substitute synthetic weights
    (binarised / region-masked values) stay exact."""
    from repro.kernels.plan import occupancy_map

    return occupancy_map(ids, vals, dim=dp, b_blk=b_blk, d_blk=d_blk)


def _plan_operands(plan, pi, pv, b: int, d: int, dp: int, b_blk: int,
                   d_blk: int, *, need_counts: bool):
    """Resolve (occ, head, headc, n_head) for a padded call.

    ``b``/``d`` are the call's *logical* row count and dim; ``pi``/``pv``
    the padded operands.  Layout mismatches degrade gracefully: a stale occ
    is replaced by the inline one, an unusable head cache by densification.
    """
    nd = dp // d_blk
    nbb = pi.shape[0] // b_blk
    occ = head = headc = None
    n_head = 0
    if plan is not None and plan.b_blk == b_blk and plan.d_blk == d_blk:
        if plan.occ is not None and plan.occ.shape == (nbb, nd):
            occ = plan.occ
        usable_head = (plan.head is not None and plan.n_head > 0
                       and plan.dim == d and plan.head.shape[0] == b
                       and plan.head.shape[1] == plan.n_head * d_blk)
        if usable_head and need_counts and plan.headc is None:
            usable_head = False          # diag needs the count twin too
        if usable_head:
            n_head = plan.n_head
            head = _pad_to(plan.head, b_blk, 0)
            headc = _pad_to(plan.headc, b_blk, 0) if need_counts else None
    if occ is None:
        occ = _inline_occ(pi, pv, dp, d_blk, b_blk)
    return occ, head, headc, n_head


@partial(jax.jit, static_argnames=("b_blk", "k_blk", "d_blk", "k_sup",
                                   "tuned", "diag", "interpret"))
def sparse_sim(ids, vals, means_t, *, plan=None, tuned=None,
               diag: bool = False, b_blk=None, k_blk=None, d_blk=None,
               k_sup: int | None = None, interpret: bool | None = None):
    """(B, K) exact similarities of padded sparse objects vs dense means.

    ``diag=True`` additionally returns the (B, K) visited-pair counts
    (live slots × nonzero mean entries) from the same launch.
    """
    interpret = _interpret(interpret)
    cfg, b_blk, k_blk, d_blk = _resolve_cfg(tuned, plan, b_blk, k_blk, d_blk)
    b, k = ids.shape[0], means_t.shape[1]
    d = means_t.shape[0]
    pi, pv, pm = _align(ids, vals, means_t, b_blk, k_blk, d_blk)
    ks = _pick_k_sup(pm.shape[1], k_blk, k_sup, cap=cfg.k_sup_cap)
    occ, head, headc, n_head = _plan_operands(
        plan, pi, pv, b, d, pm.shape[0], b_blk, d_blk, need_counts=diag)
    launch = lambda r, o: _ss.sparse_sim_pallas(
        r[0], r[1], pm, o, r[2], r[3], b_blk=b_blk, k_sup=ks, d_blk=d_blk,
        n_head=n_head, diag=diag, interpret=interpret)
    out = _map_rows(launch, (pi, pv, head, headc), occ, b_blk)
    if diag:
        sims, counts = out
        return sims[:b, :k], counts[:b, :k]
    return out[:b, :k]


@partial(jax.jit, static_argnames=("b_blk", "k_blk", "d_blk", "k_sup",
                                   "tuned", "with_sims", "diag",
                                   "interpret"))
def esicp_gather(ids, vals, means_t, t_th, v_th, *, plan=None, tuned=None,
                 with_sims: bool = False, diag: bool = False, b_blk=None,
                 k_blk=None, d_blk=None, k_sup: int | None = None,
                 interpret: bool | None = None):
    """Fused Region-1/2 exact similarity + Region-3 L1 mass.

    Returns ``(rho12, y)``, extended by the full exact similarity when
    ``with_sims`` and by the exact-region visited-pair counts when ``diag``
    — all accumulated off one densified slab per (B, D) block.
    """
    interpret = _interpret(interpret)
    cfg, b_blk, k_blk, d_blk = _resolve_cfg(tuned, plan, b_blk, k_blk, d_blk)
    b, k = ids.shape[0], means_t.shape[1]
    d = means_t.shape[0]
    pi, pv, pm = _align(ids, vals, means_t, b_blk, k_blk, d_blk)
    ks = _pick_k_sup(pm.shape[1], k_blk, k_sup, cap=cfg.k_sup_cap)
    occ, head, headc, n_head = _plan_operands(
        plan, pi, pv, b, d, pm.shape[0], b_blk, d_blk, need_counts=diag)
    launch = lambda r, o: _eg.esicp_gather_pallas(
        r[0], r[1], pm, t_th, v_th, o, r[2], r[3], b_blk=b_blk, k_sup=ks,
        d_blk=d_blk, n_head=n_head, with_sims=with_sims, diag=diag,
        interpret=interpret)
    out = _map_rows(launch, (pi, pv, head, headc), occ, b_blk)
    return tuple(o[:b, :k] for o in out)


@partial(jax.jit, static_argnames=("b_blk", "k_blk", "tuned", "interpret"))
def sketch_sim(sk_docs, sketch_t, *, plan=None, tuned=None, b_blk=None,
               k_blk=None, interpret: bool | None = None):
    """(B, K) block-vector sketch similarity — the sketch gate's dense pass.

    Zero-padding S to the 128-lane tile and K to ``k_blk`` leaves every
    retained dot product bitwise equal to the unpadded reference matmul
    (kernels/ref.py sketch_sim), which the backend parity matrix relies on.
    """
    interpret = _interpret(interpret)
    cfg, b_blk, k_blk, _ = _resolve_cfg(tuned, plan, b_blk, k_blk, None)
    b, s = sk_docs.shape
    k = sketch_t.shape[1]
    px = _pad_to(_pad_to(sk_docs, 128, 1), b_blk, 0)
    pm = _pad_to(_pad_to(sketch_t, 128, 0), k_blk, 1)
    out = _sk.sketch_sim_pallas(px, pm, b_blk=b_blk, interpret=interpret)
    return out[:b, :k]


@partial(jax.jit, static_argnames=("b_blk", "k_blk", "interpret"))
def esicp_filter(rho12, y, rho_max, col_ok, v_th, *, b_blk=128, k_blk=256,
                 interpret: bool | None = None):
    """(survivor mask int8 (B,K), |Z_i| counts (B,))."""
    interpret = _interpret(interpret)
    b, k = rho12.shape
    pr = _pad_to(_pad_to(rho12, k_blk, 1), b_blk, 0)
    py = _pad_to(_pad_to(y, k_blk, 1), b_blk, 0)
    pm = _pad_to(rho_max, b_blk, 0, value=jnp.inf)  # padding rows prune all
    pc = _pad_to(_pad_to(col_ok.astype(jnp.int8), k_blk, 1), b_blk, 0)
    mask, count = _ef.esicp_filter_pallas(pr, py, pm, pc, v_th, b_blk=b_blk,
                                          k_blk=k_blk, interpret=interpret)
    return mask[:b, :k], count[:b]


@partial(jax.jit, static_argnames=("k", "d", "b_blk", "k_blk", "d_blk",
                                   "k_sup", "tuned", "interpret"))
def segment_update(assign, ids, vals, *, k: int, d: int, plan=None,
                   tuned=None, b_blk=None, k_blk=None, d_blk=None,
                   k_sup: int | None = None,
                   interpret: bool | None = None):
    """(K, D) cluster sums λ. Padding objects get assign = k (out of range)."""
    interpret = _interpret(interpret)
    cfg, b_blk, k_blk, d_blk = _resolve_cfg(tuned, plan, b_blk, k_blk, d_blk)
    # Padded rows get assign = k: when k is block-aligned that index falls
    # past the last superblock's iota range, otherwise into a padding
    # column — either way it contributes nothing to the sliced result.
    b = ids.shape[0]
    pa = _pad_to(assign, b_blk, 0, value=k)
    pi = _pad_to(_pad_to(ids, 8, 1), b_blk, 0)
    pv = _pad_to(_pad_to(vals, 8, 1), b_blk, 0)
    kp = k + ((-k) % k_blk)
    dp = d + ((-d) % d_blk)
    ks = _pick_k_sup(kp, k_blk, k_sup, cap=cfg.k_sup_cap)
    occ, head, _, n_head = _plan_operands(
        plan, pi, pv, b, d, dp, b_blk, d_blk, need_counts=False)
    launch = partial(_su.segment_update_pallas, k=kp, d=dp, b_blk=b_blk,
                     k_sup=ks, d_blk=d_blk, n_head=n_head,
                     interpret=interpret)
    chunked = _row_chunks((pa, pi, pv, head), occ, b_blk,
                          pad_values=(k, 0, 0, 0))
    if chunked is None:
        out = launch(pa, pi, pv, occ=occ, head=head)
    else:
        # Each chunk continues the previous chunk's accumulator in place:
        # the same sequential sum over B-tiles as one launch.
        def body(lam, xs):
            (ca, ci, cv, ch), co = xs
            return launch(ca, ci, cv, occ=co, head=ch, init=lam), None

        out, _ = jax.lax.scan(body, jnp.zeros((kp, dp), jnp.float32),
                              chunked)
    return out[:k, :d]


@partial(jax.jit, static_argnames=("b_blk", "k_blk", "d_blk", "k_sup",
                                   "tuned", "interpret"))
def rho_gather(assign, ids, vals, means_t, *, plan=None, tuned=None,
               b_blk=None, k_blk=None, d_blk=None, k_sup: int | None = None,
               interpret: bool | None = None):
    """(B,) ρ_self refresh: each object's similarity vs its own centroid.

    Padding objects get assign = k (out of range) and read back ρ = 0.
    """
    interpret = _interpret(interpret)
    cfg, b_blk, k_blk, d_blk = _resolve_cfg(tuned, plan, b_blk, k_blk, d_blk)
    b = ids.shape[0]
    k = means_t.shape[1]
    d = means_t.shape[0]
    pa = _pad_to(assign, b_blk, 0, value=k)
    pi, pv, pm = _align(ids, vals, means_t, b_blk, k_blk, d_blk)
    ks = _pick_k_sup(pm.shape[1], k_blk, k_sup, cap=cfg.k_sup_cap)
    occ, head, _, n_head = _plan_operands(
        plan, pi, pv, b, d, pm.shape[0], b_blk, d_blk, need_counts=False)
    launch = lambda r, o: _rg.rho_gather_pallas(
        r[0], r[1], r[2], pm, o, r[3], b_blk=b_blk, k_sup=ks, d_blk=d_blk,
        n_head=n_head, interpret=interpret)
    out = _map_rows(launch, (pa, pi, pv, head), occ, b_blk)
    return out[:b]


@partial(jax.jit, static_argnames=("window", "sq_blk", "sk_blk", "interpret"))
def flash_attention(q, k, v, *, window: int = -1, sq_blk=128, sk_blk=128,
                    interpret: bool | None = None):
    """Banded-causal flash attention; heads folded into the batch dim."""
    interpret = _interpret(interpret)
    bh, sq, hd = q.shape
    sk = k.shape[1]
    pq = _pad_to(q, sq_blk, 1)
    pk = _pad_to(k, sk_blk, 1)
    pv = _pad_to(v, sk_blk, 1)
    out = _fa.flash_attention_pallas(pq, pk, pv, window=window,
                                     sq_blk=sq_blk, sk_blk=sk_blk,
                                     interpret=interpret, sk_real=sk)
    return out[:, :sq]

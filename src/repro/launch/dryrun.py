import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# keep bf16 in the lowered programs (CPU backend compiles bf16 fine; it just
# cannot execute it — the dry-run never executes)
os.environ.setdefault("REPRO_COMPUTE_DTYPE", "bfloat16")

"""Multi-pod dry-run driver.

For every (architecture × input shape × mesh) cell:
  jit(step).lower(*specs).compile()  →  memory_analysis + cost_analysis +
  collective-bytes parse  →  results/dryrun/<cell>.json

Nothing full-size is ever allocated: params/caches/tokens enter as
ShapeDtypeStructs.  Results are cached per cell so the sweep is resumable.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --multi-pod-only --force
    PYTHONPATH=src python -m repro.launch.dryrun --kmeans        # paper's job
"""
import argparse
import json
import time
import traceback

import jax

from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, cell_enabled
from repro.launch.steps import build_cell, reduced_depth_config, VARIANTS
from repro.roofline.analysis import (PEAKS, V5E, collective_bytes, cost_dict,
                                     model_flops, roofline_terms)

COST_KEYS = ("flops", "bytes accessed", "transcendentals")


def _measure_cost(cfg, mesh, shape, pv):
    """One unrolled compile -> (cost dict, collective dict)."""
    cell = build_cell(cfg, mesh, shape, microbatches=1, variant=pv)
    with mesh:
        compiled = cell.fn.lower(*cell.args).compile()
        cost = cost_dict(compiled.cost_analysis())
        coll = collective_bytes(compiled.as_text())
    return cost, coll


def cost_extrapolated(cfg, mesh, shape, pv) -> dict:
    """XLA counts while bodies once, so FLOPs/bytes/collectives come from two
    reduced-depth compiles with every scan UNROLLED, linearly extrapolated in
    layer count (exact for layer-homogeneous stacks; see launch/steps.py)."""
    from repro.models.config import set_scan_unroll
    set_scan_unroll(True)
    try:
        meas = {}
        for m in (1, 2):
            rcfg = reduced_depth_config(cfg, m)
            cost, coll = _measure_cost(rcfg, mesh, shape, pv)
            meas[m] = (cost, coll, rcfg.n_layers)
    finally:
        set_scan_unroll(False)
    (c1, l1_coll, n1), (c2, l2_coll, n2) = meas[1], meas[2]
    full_l = cfg.n_layers

    def extra(v1, v2):
        per_layer = (v2 - v1) / max(n2 - n1, 1)
        base = v1 - per_layer * n1
        return base + per_layer * full_l

    cost = {k: extra(float(c1.get(k, 0.0)), float(c2.get(k, 0.0)))
            for k in COST_KEYS}
    kinds = set(l1_coll) | set(l2_coll)
    coll = {k: extra(float(l1_coll.get(k, 0)), float(l2_coll.get(k, 0)))
            for k in kinds}
    return {"cost": cost, "collectives": coll,
            "depths_measured": [n1, n2], "layers_full": full_l}

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _mem_dict(mem) -> dict:
    return {k: getattr(mem, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes")}


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             out_dir: str = RESULTS_DIR, force: bool = False,
             variant: str = "baseline") -> dict:
    from repro.configs import get_config

    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    name = f"{arch}__{shape_name}__{mesh_tag}__{variant}"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, name + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    shape = SHAPES[shape_name]
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
              "variant": variant, "status": "skip"}
    if not cell_enabled(arch, shape_name):
        record["reason"] = "long_500k requires a sub-quadratic stack"
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
        return record

    if arch == "gemma3-1b" and shape_name == "long_500k":
        from repro.configs.gemma3_1b import long_context_config
        cfg = long_context_config()
    else:
        cfg = get_config(arch)

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    pv = VARIANTS[variant]
    t0 = time.time()
    try:
        cell = build_cell(cfg, mesh, shape, variant=pv)
        with mesh:
            lowered = cell.fn.lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            cost_raw = cost_dict(compiled.cost_analysis())
            coll_raw = collective_bytes(compiled.as_text())
        # correct trip-count undercounting via the unrolled reduced-depth pass
        cx = cost_extrapolated(cfg, mesh, shape, pv)
        terms = roofline_terms(cx["cost"], cx["collectives"], PEAKS[V5E])
        mf = model_flops(cfg, shape, n_chips)
        per_dev_bytes = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                         + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        record.update({
            "status": "ok",
            "n_chips": n_chips,
            "meta": cell.meta,
            "memory": _mem_dict(mem),
            "per_device_bytes": per_dev_bytes,
            "fits_hbm_16g": bool(per_dev_bytes < 16e9),
            "cost": {k: float(v) for k, v in cx["cost"].items()},
            "cost_scanned_raw": {k: float(v) for k, v in cost_raw.items()
                                 if isinstance(v, (int, float)) and k in COST_KEYS},
            "collectives": cx["collectives"],
            "collectives_scanned_raw": coll_raw,
            "cost_extrapolation": {k: cx[k] for k in
                                   ("depths_measured", "layers_full")},
            "roofline": terms,
            "model_flops": mf,
            "useful_flops_ratio": (mf["model_flops_per_dev"] /
                                   terms["flops_per_dev"]
                                   if terms["flops_per_dev"] else 0.0),
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
        })
    except Exception as e:  # a failing cell is a bug — record it loudly
        record.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]})
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=str)
    return record


def run_kmeans_dryrun(multi_pod: bool, *, out_dir: str = RESULTS_DIR,
                      force: bool = False, variant: str = "baseline",
                      obj_chunk: int = 4096, tag: str | None = None) -> dict:
    """The paper's own workload: 8.2M PubMed, K=80 000, fused ES-ICP step.

    Two passes (same trick as the LM cells): pass A (chunked) for the memory
    analysis; pass B (single chunk, TAAT scan unrolled) for exact
    FLOPs/bytes/collectives — all loops become trip-1 so XLA's once-per-while
    counting is correct without extrapolation.
    """
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.pubmed8m import config as pubmed_config
    from repro.distributed.kmeans import make_step_fn, object_axes

    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    name = f"kmeans-pubmed8m__esicp__{mesh_tag}__{tag or variant}"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, name + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    # kmeans variant grammar: flags joined by '+', e.g. "two-phase+pblock8"
    lambda_dtype = jnp.bfloat16 if "lambda-bf16" in variant else jnp.float32
    two_phase = "two-phase" in variant
    p_block = 8 if "pblock8" in variant else (4 if "pblock4" in variant else 1)
    means_dtype = jnp.bfloat16 if "means-bf16" in variant else jnp.float32
    job = pubmed_config()
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes_obj = object_axes(mesh)
    n_obj = 1
    for a in axes_obj:
        n_obj *= mesh.shape[a]
    n = job.n_docs + ((-job.n_docs) % (n_obj * obj_chunk))
    d = job.vocab + ((-job.vocab) % 256)
    k = job.k                        # 80 000 % 16 == 0
    p = 128                          # padded tuple width (nt̂ ≈ 59)

    sds = jax.ShapeDtypeStruct
    po = P(axes_obj)
    sh = lambda spec: NamedSharding(mesh, spec)
    args = (
        sds((n, p), jnp.int32), sds((n, p), jnp.float32), sds((n,), bool),
        sds((n,), jnp.int32), sds((n,), jnp.float32), sds((n,), jnp.float32),
        sds((d, k), means_dtype), sds((k,), bool),
        sds((), jnp.int32), sds((), jnp.float32), sds((), jnp.int32),
    )
    in_sh = (sh(P(axes_obj, None)), sh(P(axes_obj, None)), sh(po),
             sh(po), sh(po), sh(po),
             sh(P(None, "model")), sh(P("model")),
             sh(P()), sh(P()), sh(P()))

    def compile_pass(chunk, unroll):
        step = make_step_fn(mesh, algo="esicp", k=k, obj_chunk=chunk,
                            lambda_dtype=lambda_dtype, taat_unroll=unroll,
                            two_phase=two_phase, p_block=p_block)
        fn = jax.jit(step.__wrapped__ if hasattr(step, "__wrapped__") else step,
                     in_shardings=in_sh)
        with mesh:
            compiled = fn.lower(*args).compile()
            return (compiled.memory_analysis(),
                    cost_dict(compiled.cost_analysis()),
                    collective_bytes(compiled.as_text()))

    record = {"arch": "kmeans-pubmed8m", "shape": "esicp_step",
              "mesh": mesh_tag, "variant": variant, "status": "skip"}
    t0 = time.time()
    try:
        mem, _, _ = compile_pass(obj_chunk, False)        # pass A: memory
        _, cost, coll = compile_pass(n // n_obj, True)    # pass B: exact cost
        terms = roofline_terms(cost, coll, PEAKS[V5E])
        per_dev_bytes = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                         + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        record.update({
            "status": "ok", "n_chips": mesh.devices.size,
            "memory": _mem_dict(mem), "per_device_bytes": per_dev_bytes,
            "fits_hbm_16g": bool(per_dev_bytes < 16e9),
            "cost": {kk: float(v) for kk, v in cost.items()
                     if isinstance(v, (int, float)) and kk in
                     ("flops", "bytes accessed")},
            "collectives": coll, "roofline": terms,
            "compile_s": round(time.time() - t0, 2),
            "shapes": {"n": n, "d": d, "k": k, "p": p,
                       "obj_chunk": obj_chunk},
        })
    except Exception as e:
        record.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]})
    with open(path, "w") as f:
        json.dump(record, f, indent=1, default=str)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--kmeans", action="store_true",
                    help="dry-run the paper's pubmed8m ES-ICP step")
    ap.add_argument("--variant", default="baseline", choices=list(VARIANTS))
    ap.add_argument("--out-dir", default=RESULTS_DIR)
    args = ap.parse_args()

    meshes = []
    if not args.multi_pod_only:
        meshes.append(False)
    if not args.single_pod_only:
        meshes.append(True)

    if args.kmeans:
        for mp in meshes:
            rec = run_kmeans_dryrun(mp, out_dir=args.out_dir, force=args.force)
            print(f"kmeans-pubmed8m {'2x16x16' if mp else '16x16'}: "
                  f"{rec['status']} "
                  + (f"bottleneck={rec['roofline']['bottleneck']}"
                     if rec["status"] == "ok" else rec.get("error", "")))
        return

    from repro.configs import list_archs
    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)

    n_ok = n_skip = n_err = 0
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                rec = run_cell(arch, shape, mp, out_dir=args.out_dir,
                               force=args.force, variant=args.variant)
                tag = f"{arch:22s} {shape:12s} {'2x16x16' if mp else '16x16 '}"
                if rec["status"] == "ok":
                    n_ok += 1
                    r = rec["roofline"]
                    print(f"OK   {tag} dom={r['bottleneck']:10s} "
                          f"tc={r['t_compute_s']:.3e} tm={r['t_memory_s']:.3e} "
                          f"tl={r['t_collective_s']:.3e} "
                          f"fit={rec['fits_hbm_16g']} "
                          f"compile={rec['compile_s']}s", flush=True)
                elif rec["status"] == "skip":
                    n_skip += 1
                    print(f"SKIP {tag} ({rec.get('reason','')})", flush=True)
                else:
                    n_err += 1
                    print(f"ERR  {tag} {rec['error'][:140]}", flush=True)
    print(f"\ndone: ok={n_ok} skip={n_skip} err={n_err}")


if __name__ == "__main__":
    main()

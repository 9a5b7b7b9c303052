import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape), lower + compile the production step
under the single-pod (16×16) and multi-pod (2×16×16) meshes, print
``memory_analysis()`` (proves the program fits per-chip HBM) and
``cost_analysis()`` (FLOPs/bytes for §Roofline), and append a JSON record
(including collective-traffic accounting parsed from the partitioned HLO)
to ``experiments/dryrun/``.

The lines above MUST stay the first statements in this module: jax fixes
its platform and device count at first initialization, and the dry-run
wants 512 placeholder CPU devices. It pins the CPU so that it never takes
an accelerator the host may have.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--quiet]
"""
import argparse
import json
import time
import traceback

import jax

from repro.config import SHAPES
from repro.configs import ARCHS, ASSIGNED, get_config
from repro.launch import hlo_analysis, specs
from repro.launch.mesh import make_production_mesh
from repro.common.tree import tree_bytes


def run_one(arch: str, shape: str, *, multi_pod: bool = False,
            quiet: bool = False, out_dir: str = "experiments/dryrun",
            memory_optimized: bool = True, remat: bool = True,
            tag: str = "", **spec_kw) -> dict:
    """Lower + compile one combination; returns the result record."""
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "tag": tag,
           "memory_optimized": memory_optimized, "ok": False}
    ok, note = specs.is_applicable(arch, shape)
    if not ok:
        rec.update(skipped=True, reason=note)
        if not quiet:
            print(f"[dryrun] {arch} × {shape} × {mesh_name}: SKIP ({note})")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    t0 = time.time()
    bundle = specs.input_specs(arch, shape, mesh,
                               memory_optimized=memory_optimized, remat=remat,
                               **spec_kw)
    # Donate the mutable state: caches for serve steps, bank+opt for train —
    # decode must update its KV cache in place or HBM doubles.
    donate = (1, 2) if shape == "train_4k" else (2,)
    # The ambient mesh makes the soft sharding constraints in model
    # code (repro.common.constrain) bind to the production mesh.
    with jax.set_mesh(mesh):
        lowered = jax.jit(bundle.fn, donate_argnums=donate).lower(*bundle.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    # ---- memory analysis (proves it fits) ----------------------------
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(ma, f, None)
            if v is not None:
                mem[f] = int(v)
    except Exception as e:                      # CPU backend gaps
        mem["error"] = str(e)
    # Always include the analytic per-device argument footprint.
    arg_bytes_global = sum(tree_bytes(a) for a in bundle.args)
    mem["args_global_bytes"] = int(arg_bytes_global)

    # ---- cost analysis ------------------------------------------------
    cost = {}
    try:
        ca = compiled.cost_analysis()
        cost = {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and (
                    k in ("flops", "transcendentals") or "bytes" in k)}
    except Exception as e:
        cost["error"] = str(e)

    # ---- loop-aware analysis from partitioned HLO ---------------------
    # (XLA-CPU cost_analysis counts while bodies once — see hlo_analysis;
    # the walker multiplies scan bodies by their trip counts.)
    hlo = compiled.as_text()
    coll = hlo_analysis.collective_bytes(hlo)
    walker = hlo_analysis.analyze_module(hlo)

    # ---- base-collective audit (docs/invariants.md pass 4) ------------
    # Per-layer frozen-weight all-gathers are the FSDP executor mode;
    # a reduce-type collective at an exact base-leaf shape is an error.
    from repro.analysis.collectives import audit_collectives
    audit = audit_collectives(
        hlo, bundle.args[0], target=f"{arch}x{shape}x{mesh_name}",
        allow_kinds=("all-gather", "all-gather-start"))
    rec["base_collective_audit"] = audit.to_dict()

    flops = walker["flops"]
    hbm_bytes = walker["hbm_bytes"]
    rl = hlo_analysis.Roofline(flops=flops, hbm_bytes=hbm_bytes,
                               coll_bytes=float(walker["coll_bytes"]))

    cfg = get_config(arch)
    sh = SHAPES[shape]
    n_tokens = sh.global_batch * (sh.seq_len if sh.kind in ("train", "prefill") else 1)
    mf = hlo_analysis.model_flops(cfg, n_tokens, train=(sh.kind == "train"))
    flops_global = flops * n_dev
    rec.update(
        ok=True, n_devices=n_dev,
        n_clients=bundle.n_clients, batch_per_client=bundle.batch_per_client,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory=mem, cost=cost, collectives=coll,
        walker={k: float(v) for k, v in walker.items()},
        roofline=rl.as_dict(),
        model_flops=mf,
        useful_flops_ratio=(mf / flops_global) if flops_global else None,
        meta=bundle.meta,
    )
    if not quiet:
        print(f"[dryrun] {arch} × {shape} × {mesh_name}: OK "
              f"(lower {t_lower:.1f}s, compile {t_compile:.1f}s)")
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis: flops/dev={flops:.3e} bytes/dev={hbm_bytes:.3e}")
        print(f"  collectives: {coll}")
        print(f"  roofline: compute={rl.compute_s:.4f}s memory={rl.memory_s:.4f}s "
              f"collective={rl.collective_s:.4f}s dominant={rl.dominant}")
        if not audit.ok:
            for v in audit.violations:
                print(f"  base-collective audit: {v}")
        print(f"  MODEL_FLOPS/HLO_FLOPS = {rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'], 3)}")

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        path = os.path.join(out_dir, f"{arch}_{shape}_{mesh_name}{suffix}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def run_service(arch: str, *, n_jobs: int = 20, seq_len: int = 4096,
                batch: int = 1, multi_pod: bool = False, quiet: bool = False,
                out_dir: str = "experiments/dryrun", tag: str = "",
                replicate_base: bool = False) -> dict:
    """The promoted service case (paper Table 3: ``n_jobs`` fine-tuning
    adapters time-sharing ONE frozen base): compile the FinetuneEngine's
    compact train step at bank scale under the production mesh and audit
    the partitioned HLO for base-shaped collectives. The CI tier2-sharded
    job runs this on gemma2-27b and uploads ``base_collective_audit``."""
    from repro.analysis.collectives import audit_collectives

    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    label = f"service{n_jobs}"
    rec = {"arch": arch, "shape": label, "mesh": mesh_name, "tag": tag,
           "n_jobs": n_jobs, "ok": False}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    bundle = specs.service_specs(arch, mesh, n_jobs=n_jobs, batch=batch,
                                 seq_len=seq_len,
                                 replicate_base=replicate_base)
    with jax.set_mesh(mesh):
        lowered = jax.jit(bundle.fn, donate_argnums=(1, 2)).lower(*bundle.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = {"args_global_bytes": int(sum(tree_bytes(a) for a in bundle.args))}
    try:
        ma = compiled.memory_analysis()
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes"):
            v = getattr(ma, f, None)
            if v is not None:
                mem[f] = int(v)
    except Exception as e:                      # CPU backend gaps
        mem["error"] = str(e)

    hlo = compiled.as_text()
    audit = audit_collectives(
        hlo, bundle.args[0], target=f"{arch}x{label}x{mesh_name}",
        allow_kinds=("all-gather", "all-gather-start"))
    rec.update(
        ok=True, n_devices=mesh.devices.size,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory=mem, collectives=hlo_analysis.collective_bytes(hlo),
        base_collective_audit=audit.to_dict(), meta=bundle.meta)
    if not quiet:
        print(f"[dryrun] {arch} × {label} × {mesh_name}: "
              f"{'OK' if audit.ok else 'AUDIT FAIL'} "
              f"(lower {t_lower:.1f}s, compile {t_compile:.1f}s)")
        for v in audit.violations:
            print(f"  base-collective audit: {v}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        path = os.path.join(out_dir, f"{arch}_{label}_{mesh_name}{suffix}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    if not audit.ok:
        raise SystemExit(f"{arch} {label}: base-collective audit failed")
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    # ARCHS (not just ASSIGNED): the service case targets the paper's own
    # eval models — gemma2-27b foremost.
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="all archs × shapes")
    ap.add_argument("--quiet", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-memory-optimized", action="store_true",
                    help="paper baseline without §3.6 backward")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache for decode shapes (§Perf it13)")
    ap.add_argument("--replicate-base", action="store_true",
                    help="client-parallel with replicated base (§Perf it12)")
    ap.add_argument("--microbatch-rows", type=int, default=4)
    ap.add_argument("--capacity-factor", type=float, default=1.25)
    ap.add_argument("--service-jobs", type=int, default=None, metavar="N",
                    help="run the N-jobs-one-base service case (compact "
                         "train step at bank scale) instead of the shape "
                         "sweep; default arch gemma2-27b")
    ap.add_argument("--service-seq", type=int, default=4096,
                    help="sequence length for --service-jobs")
    ap.add_argument("--service-batch", type=int, default=1,
                    help="per-job batch for --service-jobs")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    if args.service_jobs:
        run_service(args.arch or "gemma2-27b", n_jobs=args.service_jobs,
                    seq_len=args.service_seq, batch=args.service_batch,
                    multi_pod=args.multi_pod, quiet=args.quiet,
                    out_dir=args.out, tag=args.tag,
                    replicate_base=args.replicate_base)
        return

    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = sorted(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    run_one(arch, shape, multi_pod=mp, quiet=args.quiet,
                            out_dir=args.out, tag=args.tag,
                            memory_optimized=not args.no_memory_optimized,
                            kv_quant=args.kv_quant,
                            replicate_base=args.replicate_base,
                            microbatch_rows=args.microbatch_rows,
                            capacity_factor=args.capacity_factor)
                except Exception:
                    n_fail += 1
                    print(f"[dryrun] {arch} × {shape} × multi_pod={mp}: FAIL")
                    traceback.print_exc()
    if n_fail:
        raise SystemExit(f"{n_fail} dry-run failures")


if __name__ == "__main__":
    main()

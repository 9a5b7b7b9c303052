"""Multi-job fine-tuning driver — a thin wrapper over the FinetuneEngine
(fine-tuning as a service; deliverable b).

By default it trains a REDUCED variant of the chosen arch (small enough
for the CPU) for real steps; ``--full-size`` trains the published config
on the accelerator (``chip_smoke.py`` at the repository root is the
end-to-end check that full-size fine-tuning runs on a TPU v5e).
``--peft mixed`` cycles LoRA / IA3 / prefix across jobs — heterogeneous
banks sharing one engine and one base.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --clients 4 \
      --steps 50 --seq 128 --batch 2 [--peft lora|ia3|prefix|mixed] \
      [--full-size] [--ckpt-dir DIR]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.config import AdapterConfig, FinetuneConfig
from repro.configs import ARCHS, get_config
from repro.checkpoint import save_job_state
from repro.core.adapters import DEFAULT_TARGETS
from repro.core.engine_spec import EngineSpec
from repro.models import get_model
from repro.training import FinetuneEngine, FinetuneJob, make_job_stream


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="qwen3-4b")
    ap.add_argument("--clients", type=int, default=4,
                    help="concurrent fine-tuning jobs")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=2,
                    help="per-job batch (paper uses 2)")
    ap.add_argument("--peft", default="lora",
                    choices=("lora", "ia3", "prefix", "mixed"))
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (TPU); default: reduced smoke size")
    ap.add_argument("--no-memory-optimized", action="store_true")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--mesh", nargs=2, type=int, default=None,
                    metavar=("DATA", "MODEL"),
                    help="place the engine on a (data, model) device mesh "
                         "(replicated base, job rows partitioned)")
    ap.add_argument("--obs", default=None, metavar="DIR",
                    help="attach telemetry (docs/observability.md) and write "
                         "telemetry.jsonl + metrics.prom into DIR at exit")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = cfg.reduced(n_layers=args.layers, d_model=args.d_model)

    key = jax.random.PRNGKey(0)
    base = get_model(cfg).init_params(key)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(tuple(args.mesh), ("data", "model"))
    fcfg = FinetuneConfig(max_jobs=args.clients,
                          memory_optimized=not args.no_memory_optimized)
    spec = EngineSpec(cfg=cfg, finetune=fcfg, mesh=mesh,
                      replicate_base=mesh is not None)
    obs = None
    if args.obs is not None:
        from repro.obs import Obs
        obs = Obs()
    engine = FinetuneEngine(spec, base, obs=obs)

    methods = (("lora", "ia3", "prefix") if args.peft == "mixed"
               else (args.peft,))
    jobs = []
    for c in range(args.clients):
        method = methods[c % len(methods)]
        acfg = AdapterConfig(method=method, rank=args.rank,
                             targets=DEFAULT_TARGETS[method])
        jobs.append(FinetuneJob(
            acfg=acfg, data=make_job_stream(cfg, args.batch, args.seq, seed=c),
            batch_size=args.batch, seq_len=args.seq, steps=args.steps,
            lr=args.lr, warmup_steps=max(1, args.steps // 10),
            microbatch=args.microbatch, seed=c, name=f"{method}-{c}"))
        engine.submit(jobs[-1])

    print(f"[train] {cfg.name} | {args.clients} jobs x {args.peft} "
          f"(rank {args.rank}) | seq {args.seq} batch {args.batch}")
    t0 = time.time()
    tick = 0
    while engine.pending():
        engine.train_tick()
        tick += 1
        if tick % max(1, args.steps // 10) == 0 or not engine.pending():
            losses = [round(j.losses[-1], 3) for j in jobs if j.losses]
            tok_s = engine.stats["train_tokens"] / (time.time() - t0)
            print(f"  tick {tick:4d} loss/job={losses} ({tok_s:,.0f} tok/s)")
    first = float(np.mean([j.result.losses[0] for j in jobs]))
    last = float(np.mean([j.result.losses[-1] for j in jobs]))
    print(f"[train] done: mean loss {first:.3f} -> {last:.3f} "
          f"({100 * (first - last) / first:.0f}% drop) in {time.time() - t0:.1f}s"
          f" | banks={len(engine._banks)} steps={engine.stats['train_steps']}")
    if args.ckpt_dir:
        for j in jobs:
            save_job_state(args.ckpt_dir, j.result.step, j.result.adapter,
                           j.result.opt, name=j.name)
        print(f"[train] per-job checkpoints -> "
              f"{args.ckpt_dir}/step_{jobs[0].result.step:08d}")
    if obs is not None:
        import os
        from repro.obs import export
        os.makedirs(args.obs, exist_ok=True)
        jl = os.path.join(args.obs, "telemetry.jsonl")
        pm = os.path.join(args.obs, "metrics.prom")
        export.write_jsonl(jl, obs)
        export.write_prometheus(pm, obs)
        print(f"[train] telemetry written to {jl} and {pm}")
    return first, last


if __name__ == "__main__":
    main()

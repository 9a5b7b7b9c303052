"""Multi-tenant serving driver (deliverable b).

Serves a bank of adapter clients against one shared base with the
ServingEngine (opportunistic batching). By default it runs a reduced
config (2 layers, d_model 256), small enough for the CPU; ``--full-size``
runs the published config on the accelerator. ``chip_smoke.py`` at the
repository root is the end-to-end check that full-size serving runs on a
TPU v5e.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-8b \
      --clients 4 --requests 8 --prompt-len 32 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.config import AdapterConfig, ServeConfig
from repro.configs import ARCHS, get_config
from repro.core import symbiosis
from repro.core.engine_spec import BankSpec, EngineSpec
from repro.serving.engine import ServingEngine, Request


def _mesh_from(dims):
    if dims is None:
        return None
    from repro.launch.mesh import make_mesh
    return make_mesh(tuple(dims), ("data", "model"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="granite-3-8b")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--policy", default="opportunistic",
                    choices=("lockstep", "nolockstep", "opportunistic"))
    ap.add_argument("--stagger", type=int, default=0,
                    help="ticks between request arrivals (mid-stream joins)")
    ap.add_argument("--full-size", action="store_true")
    ap.add_argument("--privacy", action="store_true")
    ap.add_argument("--page-block", type=int, default=0,
                    help="page the KV cache in blocks of this many tokens "
                         "(0 = dense max_seq-deep slot rows)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="pages per client pool (0 = full provisioning)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache entries + per-head f32 scales")
    ap.add_argument("--mesh", nargs=2, type=int, default=None,
                    metavar=("DATA", "MODEL"),
                    help="place the engine on a (data, model) device mesh "
                         "(replicated base, client axes partitioned)")
    ap.add_argument("--obs", default=None, metavar="DIR",
                    help="attach telemetry (docs/observability.md) and write "
                         "telemetry.jsonl + metrics.prom into DIR at exit")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_size:
        cfg = cfg.reduced()
    acfg = AdapterConfig(method="lora", rank=8, targets=("q", "v"))
    scfg = ServeConfig(n_clients=args.clients, policy=args.policy,
                       max_seq=args.prompt_len + args.max_new + 8,
                       page_block=args.page_block, pool_pages=args.pool_pages,
                       kv_quant=args.kv_quant)

    key = jax.random.PRNGKey(scfg.seed)
    base, bank, _ = symbiosis.init_system(cfg, acfg, args.clients, key)
    spec = EngineSpec(cfg=cfg,
                      banks=(BankSpec("tenants", acfg,
                                      capacity=args.clients),),
                      serve=scfg, mesh=_mesh_from(args.mesh),
                      replicate_base=args.mesh is not None,
                      max_batch_per_client=args.batch)
    obs = None
    if args.obs is not None:
        from repro.obs import Obs
        obs = Obs()
    eng = ServingEngine(spec, base, [bank], obs=obs)

    rng = np.random.default_rng(0)
    reqs = [Request(client_id=i % args.clients,
                    prompt=rng.integers(0, cfg.vocab,
                                        (args.batch, args.prompt_len)).astype(np.int32),
                    max_new_tokens=args.max_new,
                    arrive_tick=i * args.stagger)
            for i in range(args.requests)]
    for r in reqs:
        eng.submit(r)

    # report from engine state, not the raw args: serve_cache_kwargs drops
    # knobs a family can't honor (no KV to page on rwkv, no pure-KV cache
    # to quantize on hybrid/encdec)
    layout = (f"paged(block={scfg.page_block}, pool={eng._pool_pages})"
              if eng._paged else "dense")
    if eng._quant:
        layout += "+int8"
    print(f"[serve] {cfg.name} | {args.clients} clients | {args.requests} requests "
          f"| policy={args.policy} | kv={layout}")
    t0 = time.time()
    done = eng.run()
    dt = time.time() - t0
    total_tokens = sum(r.generated.size for r in done)
    print(f"[serve] {len(done)} requests, {total_tokens} tokens in {dt:.1f}s "
          f"({total_tokens/dt:,.0f} tok/s) | engine stats: {eng.stats}")
    sim = eng.simulate_policy(done)
    print(f"[serve] policy timeline ({args.policy}): {sim.summary()}")
    if obs is not None:
        import os
        from repro.obs import export
        os.makedirs(args.obs, exist_ok=True)
        jl = os.path.join(args.obs, "telemetry.jsonl")
        pm = os.path.join(args.obs, "metrics.prom")
        export.write_jsonl(jl, obs)
        export.write_prometheus(pm, obs)
        print(f"[serve] telemetry written to {jl} and {pm}")
    return done


if __name__ == "__main__":
    main()

"""Fig 18/19/20: heterogeneous placement.

Fig 19 (single request, growing context): all-GPU vs GPU+offloaded-cache vs
Symbiosis hetero (client on CPU). Analytic v5e/PCIe/host model
(serving.kvcache.decode_token_cost) — reproduces the paper's >=32K
crossover and the all-GPU OOM wall.
Fig 18 (hetero fine-tuning): client-side vs base-side compute split measured
on this host, showing the client share is small enough to park on a weak
device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.hardware import V5E
from repro.config import AdapterConfig, TrainConfig
from repro.configs import get_config
from repro.serving.kvcache import decode_token_cost, cache_bytes
from benchmarks.common import emit, timeit

CONTEXTS = [2_048, 8_192, 16_384, 32_768, 65_536, 131_072, 262_144, 524_288]


def run(quick: bool = False):
    cfg = get_config("symbiosis-llama2-13b")   # paper uses Llama2-7B/13B
    rows = []
    crossover = None
    for ctx in (CONTEXTS[:5] if quick else CONTEXTS):
        costs = {p: decode_token_cost(cfg, ctx, placement=p, chip=V5E)
                 for p in ("gpu", "gpu_offload", "hetero")}
        row = {"fig": "19", "context": ctx,
               "kv_cache_GB": round(cache_bytes(cfg, ctx) / 1e9, 1)}
        for p, c in costs.items():
            row[f"{p}_s_per_tok"] = (round(c.total, 4)
                                     if c.total != float("inf") else "OOM")
        if (crossover is None
                and costs["hetero"].total < costs["gpu_offload"].total):
            crossover = ctx
        rows.append(row)
    rows.append({"fig": "19", "context": "crossover_at",
                 "kv_cache_GB": crossover,
                 "gpu_s_per_tok": "-", "gpu_offload_s_per_tok": "-",
                 "hetero_s_per_tok": "paper: >=32K"})

    # Fig 18 proxy: measure client-side vs base-side compute split
    rcfg = cfg.reduced(n_layers=2, d_model=256 if quick else 512)
    acfg = AdapterConfig(method="lora", rank=8, targets=("q", "k", "v", "o"))
    from repro.core import symbiosis
    base, bank, opt = symbiosis.init_system(rcfg, acfg, 2, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 2, 128), jnp.int32),
             "labels": jnp.ones((2, 2, 128), jnp.int32)}
    full = jax.jit(symbiosis.make_multi_client_train_step(
        rcfg, acfg, TrainConfig(n_clients=2, remat=False)))
    t_full = timeit(lambda: full(base, bank, opt, batch, 0), reps=3)
    # base-only: forward through frozen matmuls alone (adapterless, no grad)
    from repro.models import get_model
    model = get_model(rcfg)
    fwd = jax.jit(lambda b: model.forward(base, b, remat=False)[0])
    t_base = timeit(lambda: fwd({"tokens": batch["tokens"][0]}), reps=3)
    rows.append({"fig": "18", "context": "base_vs_client_split",
                 "kv_cache_GB": "-",
                 "gpu_s_per_tok": round(t_base, 4),
                 "gpu_offload_s_per_tok": round(t_full, 4),
                 "hetero_s_per_tok":
                     f"client share ~{100 * max(0.0, 1 - 2 * t_base / t_full):.0f}%"})
    return emit("fig18_19_heterogeneous", rows)


if __name__ == "__main__":
    run()

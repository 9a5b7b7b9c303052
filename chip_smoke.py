#!/usr/bin/env python3
"""Smoke test of the main path on a TPU v5e, through the entry points users
call: mixed-PEFT serving and fine-tuning that share one frozen base, built
by ``EngineSpec`` + ``SymbiosisEngine.from_spec``. The model is qwen3-4b at
its published widths and all 36 layers, in bf16, with random weights made
from ``--seed``.

  python chip_smoke.py              one chip: serve, kernel check, train
  python chip_smoke.py --chips 4    four chips: the sharded engines against
                                    the one-device engine, and nothing else

One chip:

1. serve: LoRA, IA3 and prefix banks of two clients each, paged KV with
   16-token pages (compacted prefill and decode). Eight requests with
   prompts from 64 tokens up to the 2048 bucket; two of them are a chat
   request and its regeneration, which share the system prompt and the
   question, so the prefix-cache hit and the copy-on-write path run. Every request must finish with its token count,
   nothing may be quarantined or rejected, ``prefix_hits`` must be > 0, and
   the compiled programs must hold the Pallas kernels
   (``tpu_custom_call``): paged attention and SGMV in the compact decode,
   SGMV in the compact prefill (whose attention is jnp).
2. kernel check: for one client of each method, the engine's first prefill
   and first decode logits (compiled paged attention and SGMV) against a
   full-sequence ``model.forward`` of the same tokens and adapter, on the
   same chip, within ``LOGIT_TOL``.
3. train: a LoRA, an IA3 and a prefix fine-tuning job on the same base at
   seq 256, batch 2, each for its step budget with finite losses and an
   adapter that moved.

Four chips (``--chips 4``): the same requests and one fine-tune step, first
on one device, then with the base replicated and clients over ``data``,
then with the base tensor-parallel over ``model``. Each sharded engine must
hold its own shard on every device, and its first prefill and decode
logits and first-step losses must agree with the one-device engine's
(``SHARDED_LOGIT_TOL``, ``SHARDED_LOSS_TOL``); bitwise equality of logits,
token streams and adapters is reported.

The script refuses to run without a TPU (exit 2, before any phase). Any
failed check exits 1. The last line of the output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Engine logits against model.forward, both in bf16 on the same chip: the
# two paths store activations in bf16 (relative rounding 2^-8) and sum
# attention in different orders (the paged kernel's online softmax over
# 16-token pages against one dense softmax), and the difference compounds
# over 36 residual layers. The bound is on max|a - b| / max|b|. A check is
# only as sharp as the adapter's own effect on the logits, so that effect
# must exceed 2 * LOGIT_TOL: a kernel that dropped or mixed up adapters
# would then fail.
LOGIT_TOL = 0.05
# Sharded engines against the one-device engine. Tensor parallelism splits
# the o/down/lm-head contractions over 4 chips, so partial products sum in
# another order. With a replicated base each chip runs the same program on
# a quarter of the rows, which is bitwise equal to one device on CPU host
# devices but not on a v5e (logits about 1% apart), so bitwise equality is
# reported, and the bound is the same as for tensor parallelism.
SHARDED_LOGIT_TOL = 0.05
SHARDED_LOSS_TOL = 0.01       # relative, first-step cross-entropy


def noise_std(path) -> float:
    """Std of the seeded noise added to an adapter leaf: 0.1 on LoRA and
    IA3 weights; 1 on prefix keys and values, the scale of the model's own
    RMS-normalised keys and its values, so the virtual tokens draw real
    attention weight."""
    names = {getattr(k, "key", None) for k in path}
    return 1.0 if names & {"prefix_k", "prefix_v"} else 0.1


class SmokeFailure(Exception):
    """A check of the smoke test failed."""


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


@dataclasses.dataclass(frozen=True)
class Plan:
    """Sizes of one smoke run. ``FULL`` is the chip run; tests pass a small
    plan with a tiny model."""
    prompt_lens: tuple = (64, 1100, 160, 384, 700, 96)
    prompt_clients: tuple = (0, 0, 2, 3, 4, 5)
    system_len: int = 250          # chat request: system prompt + question,
    question_len: int = 20         # 270 tokens = 16 pages + 14
    shared_client: int = 1
    shared_arrive: int = 2         # tick the regeneration arrives
    max_new: int = 32
    page_block: int = 16
    max_seq: int = 2048
    pool_pages: int = 96           # per client: 1536 tokens
    check_clients: tuple = (0, 2, 4)   # one client per method
    train_seq: int = 256
    train_batch: int = 2
    train_steps: int = 3


FULL = Plan()
# four chips: fewer, shorter requests keep the three engines' compiles few
FOUR = Plan(prompt_lens=(64, 200, 120, 96), prompt_clients=(0, 2, 4, 5),
            max_new=8, train_steps=1)
METHODS = ("lora", "ia3", "prefix")


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------

def make_spec(cfg, plan: Plan, *, mesh=None, replicate_base=False):
    from repro.config import (AdapterConfig, FinetuneConfig, ServeConfig)
    from repro.core.adapters import DEFAULT_TARGETS
    from repro.core.engine_spec import BankSpec, EngineSpec
    banks = tuple(BankSpec(m, AdapterConfig(method=m,
                                            targets=DEFAULT_TARGETS[m]),
                           capacity=2) for m in METHODS)
    return EngineSpec(
        cfg=cfg, banks=banks,
        serve=ServeConfig(n_clients=6, max_seq=plan.max_seq,
                          page_block=plan.page_block,
                          pool_pages=plan.pool_pages),
        finetune=FinetuneConfig(max_jobs=len(METHODS), remat=True),
        mesh=mesh, replicate_base=replicate_base)


def init_weights(spec, seed: int):
    """Seeded base params and adapter banks. The base is built by one
    jitted program (no float32 copies of whole layer stacks), directly in
    its mesh placement when the spec has a mesh. Every bank leaf gets
    seeded noise (``noise_std``), so LoRA's B is not zero and IA3 scales
    are not one: each method changes the logits."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import shardings
    from repro.models import get_model

    cfg = spec.cfg
    model = get_model(cfg)
    key = jax.random.PRNGKey(seed)
    out_shardings = None
    if spec.mesh is not None:
        shapes = jax.eval_shape(model.init_params, key)
        specs = (jax.tree.map(lambda s: P(), shapes) if spec.replicate_base
                 else shardings.base_param_specs(cfg, spec.mesh, shapes))
        out_shardings = jax.tree.map(
            lambda p: NamedSharding(spec.mesh, p), specs)
    base = jax.jit(model.init_params, out_shardings=out_shardings)(key)

    def perturb(tree, k):
        leaves, tdef = jax.tree_util.tree_flatten_with_path(tree)
        ks = jax.random.split(k, len(leaves))
        return jax.tree.unflatten(tdef, [
            x + noise_std(path) * jax.random.normal(kk, x.shape, x.dtype)
            for (path, x), kk in zip(leaves, ks)])

    bkey = jax.random.fold_in(key, 1)
    banks = [perturb(b, jax.random.fold_in(bkey, i))
             for i, b in enumerate(spec.init_banks(bkey))]
    return base, banks


def make_requests(cfg, plan: Plan, seed: int):
    """The smoke traffic: one request per ``prompt_lens`` entry, plus a
    chat request (system prompt + question, ending mid-page) and its
    regeneration, sampled with another seed. The regeneration arrives
    while the original still decodes: it maps the original's full prompt
    pages and copies its partial tail page on write."""
    import numpy as np
    from repro.serving.engine import Request, SamplingParams

    rng = np.random.default_rng(seed)

    def toks(n):
        return rng.integers(0, cfg.vocab, size=n, dtype=np.int32)

    reqs = [Request(client_id=c, prompt=toks(n)[None],
                    max_new_tokens=plan.max_new)
            for n, c in zip(plan.prompt_lens, plan.prompt_clients)]
    chat = np.concatenate([toks(plan.system_len), toks(plan.question_len)])
    reqs.append(Request(client_id=plan.shared_client, prompt=chat[None],
                        max_new_tokens=plan.max_new))
    reqs.append(Request(client_id=plan.shared_client, prompt=chat[None],
                        max_new_tokens=plan.max_new,
                        sampling=SamplingParams("top_k", top_k=50, seed=1),
                        arrive_tick=plan.shared_arrive))
    return reqs


def record_logits(serving, keep: int = 2):
    """Keep the first ``keep`` logits blocks the engine samples from for
    each request: its prefill logits, then its first decode logits. Wraps
    the engine instance's sampler; what is sampled does not change."""
    import numpy as np

    seen = {}
    sample = serving._sample

    def recording(logits, req):
        got = seen.setdefault(id(req), [])
        if len(got) < keep:
            got.append(np.array(logits, np.float32))
        return sample(logits, req)

    serving._sample = recording
    return seen


class CompileClock:
    """Seconds spent in XLA backend compiles, read per phase."""

    def __init__(self):
        import jax
        self.total = 0.0

        def on_event(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.total += secs

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def lap(self) -> float:
        t, self.total = self.total, 0.0
        return t


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def serve_phase(service, requests) -> dict:
    """Run the requests to completion; check every one finished whole."""
    serving = service.serving
    for r in requests:
        service.submit(r)
    done, _ = service.run()
    st = serving.stats
    check(len(done) == len(requests),
          f"{len(done)}/{len(requests)} requests returned")
    for r in done:
        n = 0 if r.generated is None else r.generated.shape[1]
        check(r.status == "ok" and n == r.max_new_tokens,
              f"client {r.client_id} prompt {r.prompt.shape[1]}: "
              f"{r.status}, {n}/{r.max_new_tokens} tokens")
    check(st["quarantined_requests"] == 0 and st["quarantined_clients"] == 0
          and st["rejected_requests"] == 0,
          f"no quarantine or rejection ({st['quarantined_requests']} "
          f"quarantined, {st['rejected_requests']} rejected)")
    check(st["prefix_hits"] > 0 and st["cow_copies"] > 0,
          f"prefix_hits={st['prefix_hits']} cow_copies={st['cow_copies']}")
    return {k: st[k] for k in ("ticks", "decode_tokens", "prefill_tokens",
                               "prefill_tokens_computed", "prefix_hits",
                               "pages_shared", "cow_copies",
                               "compact_prefill_batches")}


def compiled_hot_path(serving, plan: Plan) -> dict:
    """Compiled text of the engine's own compact decode and compact prefill
    steps, lowered with its argument trees at shapes the serve phase ran:
    decode at the smallest row bucket (the last ticks), prefill at the
    first tick's admissions. With the persistent cache on, these are the
    programs the run compiled."""
    import jax.numpy as jnp
    from repro.serving import engine as serving_engine

    z = lambda *shape: jnp.zeros(shape, jnp.int32)
    banks = tuple(serving.banks)
    nb = serving._buckets[0]
    with serving._mesh_ctx():
        decode = serving._compact_step.lower(
            serving.base, banks, serving.caches, z(nb), z(nb), z(nb), z(nb),
            z(nb), jnp.ones((nb,), bool)).compile().as_text()
        nb = serving._row_bucket(len(plan.prompt_lens) + 1)
        S = serving._bucket(max(plan.prompt_lens))
        prefill = serving_engine._jit_compact_prefill(
            serving.cfg, serving.bank_cfgs, serving.scfg, serving.mesh, 0)
        prefill = prefill.lower(
            serving.base, banks, serving.caches, z(nb, S), z(nb), z(nb),
            z(nb), z(nb), z(nb), z(nb), jnp.ones((nb,), bool)
        ).compile().as_text()
    return {"compact_decode": decode, "compact_prefill": prefill}


def kernel_calls(hlo: str, name: str) -> int:
    """Pallas custom calls of kernel ``name`` in compiled HLO text."""
    return sum(1 for line in hlo.splitlines()
               if "tpu_custom_call" in line and f"/{name}/" in line)


def check_kernels_compiled(programs: dict):
    dec, pre = programs["compact_decode"], programs["compact_prefill"]
    check(kernel_calls(dec, "paged_decode_attn") > 0,
          "compact decode holds the paged-attention kernel")
    check(kernel_calls(dec, "sgmv") > 0, "compact decode holds SGMV")
    check(kernel_calls(pre, "sgmv") > 0, "compact prefill holds SGMV")


def rel_err(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def kernel_check(spec, base, banks, requests, seen, plan: Plan,
                 tol: float = LOGIT_TOL) -> dict:
    """Engine logits (prefill, first decode) of one client per method
    against a full-sequence forward of prompt + first token with that
    client's adapter; the same forward without the adapter measures how
    far the adapter moves the logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.virtlayer import make_client_ctx
    from repro.models import get_model

    model = get_model(spec.cfg)
    bare = jax.jit(lambda p, t: model.forward(p, {"tokens": t},
                                              remat=False)[0][0, -1])
    out = {}
    for c in plan.check_clients:
        req = next(r for r in requests if r.client_id == c)
        m = c // 2                       # banks hold two clients each
        acfg = spec.banks[m].acfg
        adapter = jax.tree.map(lambda x, i=c % 2: x[i], banks[m])
        toks = jnp.asarray(np.concatenate([req.prompt[0],
                                           req.generated[0, :1]])[None])
        fwd = jax.jit(lambda p, a, t, acfg=acfg: model.forward(
            p, {"tokens": t}, make_client_ctx(spec.cfg, acfg), a,
            remat=False)[0][0, -2:])
        ref = np.asarray(fwd(base, adapter, toks), np.float32)
        got = np.concatenate(seen[id(req)][:2])
        errs = [rel_err(got[i], ref[i]) for i in range(2)]
        effect = rel_err(np.asarray(bare(base, toks), np.float32), ref[1])
        print(f"  {acfg.method} client {c}: prefill err {errs[0]:.4g}, "
              f"decode err {errs[1]:.4g}, adapter effect {effect:.4g}, "
              f"tol {tol}", flush=True)
        check(max(errs) <= tol, f"{acfg.method} logits within {tol}")
        check(effect > 2 * tol,
              f"{acfg.method} adapter moves logits by more than 2x tol")
        out[acfg.method] = {"prefill_err": errs[0], "decode_err": errs[1],
                            "adapter_effect": effect}
    return out


def make_jobs(spec, plan: Plan, seed: int):
    """One fine-tuning job per bank method, seeded adapter and data."""
    from repro.training.job import FinetuneJob, make_job_stream
    return [FinetuneJob(acfg=b.acfg,
                        data=make_job_stream(spec.cfg, plan.train_batch,
                                             plan.train_seq, seed=seed + i),
                        batch_size=plan.train_batch, seq_len=plan.train_seq,
                        steps=plan.train_steps, name=f"{b.acfg.method}-job",
                        seed=seed + i)
            for i, b in enumerate(spec.banks)]


def train_phase(service, plan: Plan, jobs) -> dict:
    """Run the jobs to their step budgets; check losses and adapters."""
    import jax
    import numpy as np
    from repro.core.adapters import init_adapter

    for j in jobs:
        service.submit(j)
    _, done = service.run()
    check(len(done) == len(jobs), f"{len(done)}/{len(jobs)} jobs returned")
    out = {}
    for j in done:
        check(j.status == "finished" and j.result.step == j.steps,
              f"{j.name}: {j.status} after {j.result.step}/{j.steps} steps")
        check(len(j.losses) == j.steps and bool(np.isfinite(j.losses).all()),
              f"{j.name}: finite losses {[round(x, 4) for x in j.losses]}")
        start = init_adapter(service.finetune.cfg, j.acfg,
                             jax.random.PRNGKey(j.seed))
        moved = any(not np.array_equal(np.asarray(a), np.asarray(b))
                    for a, b in zip(jax.tree.leaves(j.result.adapter),
                                    jax.tree.leaves(start)))
        check(moved, f"{j.name}: adapter changed")
        out[j.name] = [float(x) for x in j.losses]
    st = service.finetune.stats
    check(st["quarantined"] == 0 and st["dropped_steps"] == 0,
          f"no job quarantined or step dropped ({st['quarantined']}, "
          f"{st['dropped_steps']})")
    return out


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def build_service(spec, seed: int):
    """The symbiotic engine over seeded weights, and its logits record."""
    from repro.training.service import SymbiosisEngine

    base, banks = init_weights(spec, seed)
    service = SymbiosisEngine.from_spec(spec, base, serving_banks=banks)
    return service, record_logits(service.serving)


def run_one_chip(cfg, plan: Plan, seed: int, *, check_hlo: bool = True):
    """The three one-chip phases on one symbiotic engine."""
    clock = CompileClock()

    def done(phase, t0, extra=""):
        print(f"{phase}: {time.perf_counter() - t0:.1f} s (compile "
              f"{clock.lap():.1f} s) peak_bytes_in_use={peak_bytes()} "
              f"{extra}", flush=True)

    t0 = time.perf_counter()
    spec = make_spec(cfg, plan)
    service, seen = build_service(spec, seed)
    done("setup", t0)

    print("phase serve", flush=True)
    t0 = time.perf_counter()
    requests = make_requests(cfg, plan, seed)
    served = serve_phase(service, requests)
    done("serve", t0, json.dumps(served))
    if check_hlo:
        check_kernels_compiled(compiled_hot_path(service.serving, plan))

    print("phase kernel check", flush=True)
    t0 = time.perf_counter()
    errs = kernel_check(spec, service.serving.base, service.serving.banks,
                        requests, seen, plan)
    done("kernel check", t0)

    print("phase train", flush=True)
    t0 = time.perf_counter()
    losses = train_phase(service, plan, make_jobs(spec, plan, seed))
    done("train", t0, f"steps={service.finetune.stats['train_steps']} "
                      f"losses={json.dumps(losses)}")
    return {"served": served, "logit_errors": errs, "losses": losses,
            "peak_bytes_in_use": peak_bytes()}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def run_engine(spec, plan: Plan, seed: int) -> dict:
    """Serve the plan's requests and take one fine-tune step on one engine;
    return what the comparison reads, then drop the engine."""
    import jax
    import numpy as np

    service, seen = build_service(spec, seed)
    requests = make_requests(spec.cfg, plan, seed)
    serve_phase(service, requests)
    jobs = make_jobs(spec, plan, seed)
    train_phase(service, plan, jobs)
    return {
        "tokens": [r.generated for r in requests],
        "logits": [seen[id(r)] for r in requests],
        "losses": [j.losses[0] for j in jobs],
        "adapters": [jax.tree.map(np.asarray, j.result.adapter)
                     for j in jobs],
        "sharded": {"base": service.serving.base,
                    "caches": service.serving.caches},
    }


def compare(got: dict, ref: dict) -> dict:
    """How far a sharded engine's outputs are from the one-device
    engine's: first-token (prefill) logits of every request, first-decode
    logits where the first token agrees, token streams, first-step losses
    and the adapters after that step."""
    import jax
    import numpy as np

    pre = [rel_err(a[0], b[0]) for a, b in zip(got["logits"], ref["logits"])]
    dec = [rel_err(a[1], b[1]) for a, b, ta, tb in zip(
        got["logits"], ref["logits"], got["tokens"], ref["tokens"])
        if ta[0, 0] == tb[0, 0]]
    return {
        "prefill_logit_err": max(pre),
        "decode_logit_err": max(dec) if dec else None,
        "logits_bitwise": all(np.array_equal(a, b) for x, y in zip(
            got["logits"], ref["logits"]) for a, b in zip(x, y)),
        "identical_streams": int(sum(np.array_equal(a, b) for a, b in
                                     zip(got["tokens"], ref["tokens"]))),
        "streams": len(ref["tokens"]),
        "loss_err": max(abs(a - b) / abs(b) for a, b in
                        zip(got["losses"], ref["losses"])),
        "adapters_bitwise": all(np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(got["adapters"]),
            jax.tree.leaves(ref["adapters"]))),
    }


def own_shards(tree, n: int) -> bool:
    """True iff some leaf of ``tree`` has ``n`` addressable shards on ``n``
    distinct devices holding pairwise different index ranges."""
    import jax
    for x in jax.tree.leaves(tree):
        shards = x.addressable_shards
        if len(shards) != n or len({s.device for s in shards}) != n:
            continue
        idx = {tuple((sl.start, sl.stop) for sl in s.index) for s in shards}
        if len(idx) == n:
            return True
    return False


def run_four_chips(cfg, plan: Plan, seed: int) -> dict:
    """The one-device engine, then the replicated-base and tensor-parallel
    engines on the same inputs; every comparison is printed before any
    failed check stops the run."""
    import gc

    import jax
    from repro.launch.mesh import make_mesh

    n = len(jax.devices())
    print("engine: one device", flush=True)
    ref = run_engine(make_spec(cfg, plan), plan, seed)
    ref.pop("sharded")
    gc.collect()            # the engines hold reference cycles: free HBM
    out, failed = {}, []
    for name, shape, replicate in (("replicated", (n, 1), True),
                                   ("tensor_parallel", (1, n), False)):
        print(f"engine: {name} over {n} devices", flush=True)
        mesh = make_mesh(shape, ("data", "model"))
        got = run_engine(make_spec(cfg, plan, mesh=mesh,
                                   replicate_base=replicate), plan, seed)
        where = "caches" if replicate else "base"
        held = own_shards(got.pop("sharded")[where], n)
        diff = out[name] = compare(got, ref)
        print(f"  {name} vs one device: {json.dumps(diff)}", flush=True)
        for ok, what in (
                (held, f"each of {n} devices holds its own {where} shard"),
                (diff["prefill_logit_err"] <= SHARDED_LOGIT_TOL
                 and (diff["decode_logit_err"] or 0) <= SHARDED_LOGIT_TOL,
                 f"logits within {SHARDED_LOGIT_TOL} of one device"),
                (diff["loss_err"] <= SHARDED_LOSS_TOL,
                 f"first-step losses within {SHARDED_LOSS_TOL}")):
            print(f"  {'ok' if ok else 'FAILED'}: {name}: {what}", flush=True)
            if not ok:
                failed.append(f"{name}: {what}")
        del got
        gc.collect()
    if failed:
        raise SmokeFailure("; ".join(failed))
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {dev.platform!r};"
              " no phase was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.common.compile_cache import enable_compile_cache
    from repro.configs import get_config

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = get_config("qwen3-4b")
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab}, {cfg.param_dtype}", flush=True)
    print(f"device: {dev.device_kind} x {len(devices)}", flush=True)
    try:
        if args.chips == 4:
            result = run_four_chips(cfg, FOUR, args.seed)
        else:
            result = run_one_chip(cfg, FULL, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"result: {json.dumps(result, default=str)}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Fine-tuning as a service: several jobs train against one frozen base
for the whole window, and the driver times the engine's train ticks.

The mix file gives:

- ``jobs``: one entry per job ({name, method, rank, alpha, targets,
  n_prefix}); jobs of one method and rank share a bank (one compiled step);
- ``seq`` and ``rows``: each job's per-step batch, ``rows`` sequences of
  ``seq`` tokens, drawn from the seed so that every row of every step
  differs;
- ``remat``: the engine's activation checkpointing;
- ``opt``: the AdamW settings of every job ({lr, warmup, total, wd,
  max_grad_norm});
- ``check``: how many first steps the reference follows (it follows every
  job) and the limits on the three numbers compared;
- ``trace``: ``{offset_s, seconds}`` of the profiler window in a traced run.

Set-up builds the engine once, submits the jobs with adapters drawn from
the seed, and drives it through the checked first steps through the same
``tick()`` the window calls: those steps compile every program the window
uses. The first step's gradients are read back from the optimizer's first
moment (``m = (1 - b1) * g`` after one step), the parameters' change after
the checked steps from the adapters. The same engine then runs the window.
"""
from __future__ import annotations

import gc
import os
import time

import numpy as np

B1 = 0.9
STAGED = 64     # steps of each job's batches put on the device in set-up
TINY = 1e-3     # a leaf whose reference gradient is under this share of the
                # median leaf's moves by round-off alone and is not compared


class Stream:
    """A job's batches: ``rows`` rows of ``seq + 1`` tokens per step, from
    (seed, job, step). ``stage`` puts the first steps' batches on the
    device ahead of time, as a prefetching loader does, so that the
    window's host loop neither draws nor copies them."""

    def __init__(self, seed: int, job: int, rows: int, seq: int, vocab: int):
        self.key = (int(seed) % (1 << 63), 4, job)
        self.rows, self.seq, self.vocab = rows, seq, vocab
        self.staged = {}

    def stage(self, steps: int):
        import jax
        self.staged = dict(enumerate(jax.device_put(
            [self.numpy(s) for s in range(steps)])))

    def numpy(self, step: int) -> dict:
        rng = np.random.default_rng(self.key + (int(step),))
        t = rng.integers(0, self.vocab, size=(self.rows, self.seq + 1),
                         dtype=np.int32)
        return {"tokens": t[:, :-1], "labels": t[:, 1:]}

    def batch(self, step: int) -> dict:
        import jax.numpy as jnp
        if step in self.staged:
            return self.staged.pop(step)
        return {k: jnp.asarray(v) for k, v in self.numpy(step).items()}


def method_of(job: dict) -> dict:
    return {"name": job["name"], "method": job["method"],
            "rank": job.get("rank", 0), "alpha": job.get("alpha", 16.0),
            "targets": job.get("targets", []),
            "n_prefix": job.get("n_prefix", 0)}


def build(run, obs):
    import jax
    from repro.config import AdapterConfig, FinetuneConfig, ModelConfig
    from repro.core.engine_spec import EngineSpec
    from repro.optim import adamw_init
    from repro.training.job import FinetuneJob
    from repro.training.service import SymbiosisEngine

    from bench import weights
    md, mix = run.config["model"], run.traffic
    t = time.perf_counter()
    base = weights.make_base(md, run.seed, run.family)
    adapters = []
    for i, j in enumerate(mix["jobs"]):
        bank = weights.make_bank(md, method_of(j), run.seed, j["name"], 1,
                                 family=run.family)
        adapters.append(jax.tree.map(lambda x: x[0], bank))
    jax.block_until_ready((base, adapters))
    t_weights = time.perf_counter() - t
    spec = EngineSpec(cfg=ModelConfig(**md), finetune=FinetuneConfig(
        max_jobs=len(mix["jobs"]), remat=mix["remat"]))
    svc = SymbiosisEngine.from_spec(spec, base, obs=obs)
    opt = mix["opt"]
    jobs = []
    for i, j in enumerate(mix["jobs"]):
        acfg = AdapterConfig(method=j["method"], rank=j.get("rank") or 8,
                             alpha=j.get("alpha") or 16.0,
                             targets=tuple(j.get("targets", ("q", "v"))),
                             n_prefix=j.get("n_prefix") or 16)
        jobs.append(FinetuneJob(
            acfg=acfg, data=Stream(run.seed, i, mix["rows"], mix["seq"],
                                   md["vocab"]),
            batch_size=mix["rows"], seq_len=mix["seq"], steps=1 << 30,
            lr=opt["lr"], weight_decay=opt["wd"],
            warmup_steps=opt["warmup"], total_steps=opt["total"],
            max_grad_norm=opt["max_grad_norm"], name=j["name"],
            init_adapter=adapters[i], init_opt=adamw_init(adapters[i]),
            start_step=0))
    for job in jobs:
        job.data.stage(STAGED)
        svc.submit(job)
    jax.block_until_ready([job.data.staged for job in jobs])
    return svc, jobs, adapters, {"weights_s": t_weights}


def leaf_norms(tree) -> list:
    import jax
    import jax.numpy as jnp
    return [float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))
            for x in jax.tree.leaves(tree)]


def first_steps(run, svc, jobs, adapters) -> dict:
    """Drive the engine through the checked steps by ``tick()`` and read
    back per job: the losses, the first gradient's leaf norms from the
    optimizer's first moment, and the leaf norms of the parameters'
    change."""
    import jax
    ft = svc.finetune
    steps = run.traffic["check"]["steps"]
    seen = {"loss": None, "grad": [], "change": []}
    for s in range(steps):
        svc.tick()
        if s == 0:
            for job in jobs:
                _, opt, _ = ft.job_state(job)
                seen["grad"].append([n / (1 - B1)
                                     for n in leaf_norms(opt.m)])
    for job, a0 in zip(jobs, adapters):
        a, _, _ = ft.job_state(job)
        seen["change"].append(leaf_norms(jax.tree.map(
            lambda x, y: x.astype("float32") - y, a, a0)))
    seen["loss"] = [list(job.losses[:steps]) for job in jobs]
    return seen


def run(run):
    import jax

    from repro.obs import Obs

    from bench import harness
    mix = run.traffic
    obs = Obs() if run.traced else None
    compiles = harness.CompileCount()
    svc, jobs, adapters, setup = build(run, obs)
    ft = svc.finetune
    t = time.perf_counter()
    seen = first_steps(run, svc, jobs, adapters)
    setup["first_steps_s"] = time.perf_counter() - t
    setup["setup_compiles"], setup["setup_compile_s"] = compiles.n, \
        compiles.secs
    run.note("setup", setup)

    setup_peak = harness.peak_bytes(run.devices)
    t0 = time.perf_counter()
    run.metrics["setup_s"] = t0 - run.t_start
    t1 = t0 + run.seconds
    c0 = compiles.n
    prof = None
    if run.traced:
        prof = harness.Profile(os.path.join(run.root, "bench", ".out",
                                            "trace"))
        tr_at = t0 + mix["trace"]["offset_s"]
    ticks = []       # (start, end, tokens)
    snaps = {}
    pauses = harness.GcPauses()
    while True:
        now = time.perf_counter()
        if prof is not None and prof.t0 is None and now >= tr_at:
            prof.start()
            snaps["tr0"] = (prof.t0, len(ticks))
        if prof is not None and prof.t0 is not None and prof.t1 is None \
                and now >= prof.t0 + mix["trace"]["seconds"]:
            snaps["tr1"] = (time.perf_counter(), len(ticks))
            prof.stop()
        if now >= t1 and (prof is None or prof.t1 is not None):
            break
        before = ft.stats["train_tokens"]
        svc.tick()
        ticks.append((now, time.perf_counter(),
                      ft.stats["train_tokens"] - before))
    run.note("gc_pauses", pauses.stop())
    run.note("compiles_in_window", compiles.n - c0)
    run.memory_peak = harness.peak_bytes(run.devices)
    run.memory = {"setup_peak_bytes": setup_peak,
                  "in_use_after_window_bytes": harness.in_use_bytes(
                      run.devices)}
    run.note("memory", run.memory)
    if prof is not None:
        run.trace = prof.reduce()
    inside = [t for t in ticks if t[0] < t1]
    run.metrics["train_tokens_per_s"] = sum(t[2] for t in inside) / (
        inside[-1][1] - t0)
    took = sorted(t[1] - t[0] for t in inside)
    run.note("window", {"ticks": len(inside),
                        "tokens": sum(t[2] for t in inside),
                        "seconds": inside[-1][1] - t0,
                        "steps": ft.stats["train_steps"],
                        "tick_s": {"shortest": took[0],
                                   "median": float(np.median(took)),
                                   "longest": took[-1]}})
    from types import SimpleNamespace
    run.view = SimpleNamespace(cfg=run.config["model"], family=run.family,
                               mix=mix, ticks=ticks, snaps=snaps,
                               trace=run.trace, peaks=harness.peaks(run))
    run.attempted = len(jobs)
    run.failed = sum(1 for j in jobs if j.status != "active")

    del svc, ft, jobs, adapters, obs
    gc.collect()
    jax.clear_caches()
    compare(run, seen)
    run.correct = run.correct and run.failed == 0


def gaps(prog: list, ref: list, ref_grad: list) -> float:
    """Worst leaf gap between two lists of leaf norms: |prog - ref| over
    the larger of the reference leaf's norm and the median leaf's, over the
    leaves whose reference gradient is not nought to rounding."""
    med = float(np.median(ref))
    gmed = float(np.median(ref_grad))
    out = 0.0
    for p, r, g in zip(prog, ref, ref_grad):
        if g < TINY * gmed:
            continue
        out = max(out, abs(p - r) / max(r, med, 1e-30))
    return out


def bank_row_dropped(mix: dict) -> dict:
    """The planted fault of a bank that reads only its first job's rows:
    job index -> the job whose data it trains on, the first job of its
    bank (jobs of one method, rank, targets and prefix length share one)."""
    first = {}
    out = {}
    for i, j in enumerate(mix["jobs"]):
        m = method_of(j)
        key = (m["method"], m["rank"], tuple(m["targets"]), m["n_prefix"])
        out[i] = first.setdefault(key, i)
    return out


def follow(run, *, lowp: str = "", rows=None, data_of=None) -> list:
    """The plain reference through the checked steps of every job (the two
    jobs of a bank are two rows of one compiled step, and each is checked):
    per job its index, losses and the leaf norms of its first (clipped)
    gradient and of its parameters' change. ``rows`` keeps only the first
    rows of each batch (the planted fault: half of the batch left out);
    ``data_of`` maps a job to the job whose batches it trains on
    (``bank_row_dropped``)."""
    import jax

    from bench.reference import TrainReference
    mix, md = run.traffic, run.config["model"]
    steps = mix["check"]["steps"]
    ref = TrainReference(md, run.seed, lowp=lowp, family=run.family)
    out = []
    for i, j in enumerate(mix["jobs"]):
        meth = method_of(j)
        a0 = ref.adapter_init(meth, j["name"], 0)
        stream = Stream(run.seed, (data_of or {}).get(i, i), mix["rows"],
                        mix["seq"], md["vocab"])
        batches = [{k: v[:rows] for k, v in stream.numpy(s).items()}
                   for s in range(steps)]
        losses, g1, a = ref.run(meth, a0, batches, mix["opt"], steps)
        out.append({"job": i, "loss": losses, "grad": leaf_norms(g1),
                    "change": leaf_norms(jax.tree.map(lambda x, y: x - y,
                                                      a, a0))})
    return out


def worst(got: dict, ref: list) -> dict:
    """The three compared numbers, worst over the followed jobs: each
    step's loss gap relative to the reference's loss, and the worst-leaf
    gaps of the first gradient and of the change (``gaps``); ``got`` holds
    ``loss``, ``grad`` and ``change`` readings indexed by job."""
    out = {"loss": 0.0, "grad": 0.0, "change": 0.0}
    for r in ref:
        i = r["job"]
        out["loss"] = max(out["loss"], max(
            abs(p - q) / abs(q) for p, q in zip(got["loss"][i], r["loss"])))
        out["grad"] = max(out["grad"], gaps(got["grad"][i], r["grad"],
                                            r["grad"]))
        out["change"] = max(out["change"], gaps(got["change"][i],
                                                r["change"], r["grad"]))
    return out


def as_seen(followed: list) -> dict:
    """Readings of the reference laid out as ``first_steps`` lays out the
    program's, job by job."""
    return {k: {f["job"]: f[k] for f in followed}
            for k in ("loss", "grad", "change")}


def compare(run, seen):
    t = time.perf_counter()
    got = worst(seen, follow(run))
    lim = run.traffic["check"]
    run.note("reference", {"jobs": len(run.traffic["jobs"]),
                           "seconds": time.perf_counter() - t})
    ok = [run.check(f"{k}_gap", got[k], lim[k]) for k in
          ("loss", "grad", "change")]
    run.correct = all(ok)

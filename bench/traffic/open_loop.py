"""Open-loop serving traffic and its driver.

Requests follow a schedule of due times on the wall clock and are handed
on when due, whether or not earlier ones finished; every latency is timed
from the due time. The schedule is the same for every seed: inter-arrival
gaps, prompt and output lengths and client choices are drawn once from the
mix's ``sizes_seed``; ``--seed`` draws the prompt tokens (and the weights).
So seeds change what the work computes, not how much there is or when it
comes: with bursty arrivals near the knee, another order of the same
requests moved the tail latencies by a factor of two from seed to seed
(PERF.md).

The mix file gives:

- ``banks``: adapter banks ({name, method, rank, alpha, targets, n_prefix,
  clients}); clients are numbered bank after bank, as the engine numbers
  them;
- ``popularity.zipf_s``: client popularity by Zipf rank, ranks dealt to the
  banks in turn so that each bank holds popular and rare clients;
- ``arrivals``: ``{"process": "gamma" | "poisson", "cv", "rate_rps"}``;
- ``prompt`` / ``output``: lognormal ``{median, sigma, min, max}`` lengths;
- ``serve``: the engine's ``max_seq``, ``page_block``, ``pool_pages`` and
  ``max_batch_per_client``;
- ``load``: ``"below_knee"`` (the tails of the requests due in the window
  are the end-to-end metrics, and every one of them has to finish) or
  ``"above_knee"`` (the queue grows all through the run; tokens per second
  are the end-to-end metric, and a request still queued at the end is
  late, not failed);
- ``ramp_s``: seconds of traffic before the window opens (0: the window
  opens with the first due request);
- ``drain_s``: how long past the window's close the run waits for the
  requests it has to see finish;
- ``trace``: ``{offset_s, seconds}`` of the profiler window in a traced run;
- ``check``: ``retain_tokens``, ``tokens`` and the limits on the two numbers
  compared (see ``sample``).

Each request is submitted to the engine when it is due; the engine's own
queue and admission decide when it runs. Requests due inside the window are
the measured set. A request that is rejected or quarantined is failed: it
counts in ``failed``, misses every latency limit, and makes the run
incorrect; below the knee so is one still unfinished when the wait ends.
"""
from __future__ import annotations

import gc
import math
import os
import time

import numpy as np

# ---------------------------------------------------------------------------
# the schedule
# ---------------------------------------------------------------------------

def clients_of(mix: dict) -> list:
    """[(global client id, bank index, index in bank)] in engine order."""
    out = []
    for b, bank in enumerate(mix["banks"]):
        for i in range(bank["clients"]):
            out.append((len(out), b, i))
    return out


def zipf_order(mix: dict) -> list:
    """Global client ids from most to least popular: rank r goes to bank
    r mod n_banks, the next free client there."""
    nb = len(mix["banks"])
    offs = np.cumsum([0] + [b["clients"] for b in mix["banks"]])
    taken = [0] * nb
    order, r = [], 0
    while len(order) < offs[-1]:
        b = r % nb
        if taken[b] < mix["banks"][b]["clients"]:
            order.append(int(offs[b] + taken[b]))
            taken[b] += 1
        r += 1
    return order


def _lognormal(rng, n, spec):
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def _phase(mix: dict, phase: int, seconds: float) -> dict:
    """``round(rate * seconds)`` requests due in ``[0, seconds)``: gaps
    (scaled to span the phase exactly), sizes and clients drawn from
    ``sizes_seed``, clients in a random order of their Zipf counts."""
    arr = mix["arrivals"]
    rate = float(arr["rate_rps"])
    n = int(round(rate * seconds))
    if n == 0:
        return {k: np.zeros(0, np.int64) for k in
                ("due", "client", "prompt_len", "out_len")}
    rng0 = np.random.default_rng([mix.get("sizes_seed", 0), phase])
    if arr["process"] == "poisson":
        gaps = rng0.exponential(1.0 / rate, n)
    elif arr["process"] == "gamma":
        k = 1.0 / arr["cv"] ** 2
        gaps = rng0.gamma(k, 1.0 / (rate * k), n)
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    gaps *= seconds / gaps.sum()
    prompts = _lognormal(rng0, n, mix["prompt"])
    outputs = _lognormal(rng0, n, mix["output"])
    order = zipf_order(mix)
    w = 1.0 / np.arange(1, len(order) + 1) ** mix["popularity"]["zipf_s"]
    counts = np.floor(w / w.sum() * n).astype(np.int64)
    rest = n - counts.sum()
    counts[np.argsort(-(w / w.sum() * n - counts))[:rest]] += 1
    clients = rng0.permutation(np.repeat(np.array(order), counts))
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return {"due": due, "client": clients, "prompt_len": prompts,
            "out_len": outputs}


def schedule(mix: dict, seconds: float) -> dict:
    """Every request of a run, by due offset (s) from the start: the ramp
    (``ramp_s``), the window (``seconds``) and the load that goes on after
    it (``drain_s``), each phase drawn on its own, so that the window holds
    ``round(rate * seconds)`` requests."""
    parts, t = [], 0.0
    for phase, d in enumerate((mix.get("ramp_s", 0.0), seconds,
                               mix["drain_s"])):
        p = _phase(mix, phase, d)
        p["due"] = p["due"] + t
        parts.append(p)
        t += d
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def prompt_tokens(seed: int, i: int, length: int, vocab: int):
    rng = np.random.default_rng([int(seed) % (1 << 63), 2, i])
    return rng.integers(0, vocab, size=(1, int(length)), dtype=np.int32)


# ---------------------------------------------------------------------------
# engine construction and warm-up
# ---------------------------------------------------------------------------

def build(run, obs):
    """Seeded weights and banks, then the engine, through the users' entry
    point: ``EngineSpec`` -> ``SymbiosisEngine.from_spec``."""
    from repro.config import AdapterConfig, ModelConfig, ServeConfig
    from repro.core.engine_spec import BankSpec, EngineSpec
    from repro.training.service import SymbiosisEngine

    from bench import weights
    md, mix = run.config["model"], run.traffic
    cfg = ModelConfig(**md)
    t = time.perf_counter()
    base = weights.make_base(md, run.seed, run.family)
    banks = [weights.make_bank(md, b, run.seed, b["name"], b["clients"],
                               family=run.family) for b in mix["banks"]]
    import jax
    jax.block_until_ready((base, banks))
    t_weights = time.perf_counter() - t
    specs = tuple(BankSpec(b["name"], AdapterConfig(
        method=b["method"], rank=b.get("rank") or 8,
        alpha=b.get("alpha") or 16.0, targets=tuple(b["targets"]),
        n_prefix=b.get("n_prefix") or 16), capacity=b["clients"])
        for b in mix["banks"])
    sv = mix["serve"]
    spec = EngineSpec(cfg=cfg, banks=specs, serve=ServeConfig(
        n_clients=sum(b["clients"] for b in mix["banks"]),
        max_seq=sv["max_seq"], page_block=sv["page_block"],
        pool_pages=sv["pool_pages"]),
        max_batch_per_client=sv["max_batch_per_client"])
    t = time.perf_counter()
    svc = SymbiosisEngine.from_spec(spec, base, serving_banks=banks, obs=obs)
    jax.block_until_ready(svc.serving.caches)
    return svc, {"weights_s": t_weights, "engine_s": time.perf_counter() - t}


def warm_shapes(mix: dict, eng) -> dict:
    """Every program shape the mix's traffic can reach: each decode row
    bucket, and each compact prefill of (row bucket) x (prompt bucket from
    the shortest to the longest prompt), with no shared prefix. The engine
    prefills all of a tick's admissions in one program, so any row bucket
    up to its whole slot count can come, up to ``serve.prefill_tokens_max``
    (rows x padded prompt): the largest prefill whose temporaries fit on
    the chip beside the weights and the page pool."""
    seqs = sorted({eng._bucket(n) for n in range(mix["prompt"]["min"],
                                                 mix["prompt"]["max"] + 1)})
    most = mix["serve"].get("prefill_tokens_max", math.inf)
    return {"decode": list(eng._buckets),
            "prefill": [(nb, S) for nb in eng._buckets for S in seqs
                        if nb * S <= most]}


def warm(eng, shapes: dict):
    """Run each program once on masked rows (every write dropped): the
    jitted entries the ticks call, with the argument types the ticks pass."""
    import jax.numpy as jnp

    from repro.serving import engine as serving_engine
    z = lambda *s: jnp.asarray(np.zeros(s, np.int32))
    no = lambda n: jnp.asarray(np.zeros((n,), bool))
    eng._sync_tbl()
    for nb in shapes["decode"]:
        lg, fin, eng.caches = eng._compact_step(
            eng.base, tuple(eng.banks), eng.caches, z(nb), z(nb), z(nb),
            z(nb), z(nb), no(nb))
        np.asarray(lg)
    fn = serving_engine._jit_compact_prefill(eng.cfg, eng.bank_cfgs,
                                             eng.scfg, eng.mesh, 0)
    for nb, S in shapes["prefill"]:
        lg, fin, eng.caches = fn(eng.base, tuple(eng.banks), eng.caches,
                                 z(nb, S), z(nb), z(nb), z(nb), z(nb), z(nb),
                                 z(nb), no(nb))
        np.asarray(lg)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class Rec:
    """One scheduled request and what happened to it."""
    __slots__ = ("i", "due", "client", "S", "n", "req", "admit_tick",
                 "finish_tick")

    def __init__(self, i, due, client, S, n):
        self.i, self.due, self.client, self.S, self.n = i, due, client, S, n
        self.req = None
        self.admit_tick = self.finish_tick = None


def setup(run):
    """Engine, weights and warmed programs; set-up facts on an earlier
    line."""
    from repro.obs import Obs

    from bench import harness
    obs = Obs() if run.traced else None
    compiles = harness.CompileCount()
    svc, facts = build(run, obs)
    shapes = warm_shapes(run.traffic, svc.serving)
    t = time.perf_counter()
    warm(svc.serving, shapes)
    facts["warm_s"] = time.perf_counter() - t
    facts["warm_programs"] = len(shapes["decode"]) + len(shapes["prefill"])
    facts["setup_compiles"], facts["setup_compile_s"] = compiles.n, \
        compiles.secs
    run.note("setup", facts)
    return svc, obs, compiles, shapes


def below_knee(mix: dict) -> bool:
    if mix["load"] not in ("below_knee", "above_knee"):
        raise ValueError(f"unknown load {mix['load']!r}")
    return mix["load"] == "below_knee"


def pool_pages(eng) -> tuple:
    """(pages in the pool, pages held by requests, pages held or promised
    to the requests in flight) from the engine's page allocator."""
    if not getattr(eng, "_paged", False):
        return 0, 0, 0
    total = eng.n_clients * eng._pool_pages
    held = total - sum(len(f) for f in eng._free_pages)
    return total, held, held + sum(eng._reserved)


def window(run, svc, obs, compiles, mix, seed, seconds, t_first,
           traced=False) -> dict:
    """Drive the engine by the schedule from ``t_first``: the window is
    ``[t_first + ramp_s, + seconds)``; load goes on by the same schedule
    until the requests the run has to see finish have finished (the
    retained ones, see ``sample``, and below the knee every request due in
    the window), or ``drain_s`` past the window's close."""
    from repro.serving.engine import Request

    from bench import harness
    md = run.config["model"]
    eng = svc.serving
    sched = schedule(mix, seconds)
    recs = [Rec(i, float(sched["due"][i]) + t_first, int(sched["client"][i]),
                int(sched["prompt_len"][i]), int(sched["out_len"][i]))
            for i in range(len(sched["due"]))]
    below = below_knee(mix)
    t0 = t_first + mix.get("ramp_s", 0.0)
    t1 = t0 + seconds
    measured = [r for r in recs if t0 <= r.due < t1]
    kept = {}                  # request index -> its logits, one per token
    sampled = set()            # requests whose first token has come
    budget = mix["check"]["retain_tokens"]
    prof = None
    if traced:
        prof = harness.Profile(os.path.join(run.root, "bench", ".out",
                                            "trace"))
        tr_at = t0 + mix["trace"]["offset_s"]
    nxt = 0                    # next request to come due
    waiting = []               # submitted, not yet admitted
    ticks = []                 # (start, end) of each tick
    snaps = {}                 # name -> (time, tick index, stats, spans)
    late = []
    by_req = {}
    c_win = None
    backlog = 0
    mem = {"pool_pages": pool_pages(eng)[0], "pages_held_max": 0,
           "pages_promised_max": 0}
    most_rows = 0              # the most rows one tick's prefill held
    deadline = t1 + mix["drain_s"]
    engine_sample = eng._sample

    def keep(logits, req):
        """The engine's own sampler, keeping the logits of the requests
        that the run retains (what is sampled does not change): each
        request whose first token comes once the window has opened (above
        the knee: while it is open), until ``retain_tokens`` output tokens
        are retained."""
        r = by_req.get(id(req))
        if r is not None and r.i not in sampled:
            sampled.add(r.i)
            now = time.perf_counter()
            if t0 <= now and (below or now < t1) and \
                    sum(recs[i].n for i in kept) < budget:
                kept[r.i] = []
        if r is not None and r.i in kept:
            kept[r.i].append(np.array(logits[0], np.float32))
        return engine_sample(logits, req)

    eng._sample = keep

    def snap(name):
        spans = {}
        if obs is not None:
            for ph in ("admit", "prefill", "compact_gather", "jit_dispatch",
                       "device_sync", "scatter", "prefill_compact_gather"):
                spans[ph] = obs.metrics.histogram("span_seconds",
                                                  phase=ph).total
            spans["tick"] = obs.metrics.histogram("tick_seconds",
                                                  engine="serving").total
        snaps[name] = (time.perf_counter(), len(ticks), dict(eng.stats),
                       spans)

    def seen():
        return (not below or all(r.finish_tick is not None
                                 for r in measured)) and \
            all(recs[i].finish_tick is not None for i in kept)

    while True:
        now = time.perf_counter()
        while nxt < len(recs) and recs[nxt].due <= now:
            r = recs[nxt]
            late.append(now - r.due)
            r.req = Request(client_id=r.client,
                            prompt=prompt_tokens(seed, r.i, r.S,
                                                 md["vocab"]),
                            max_new_tokens=r.n)
            svc.submit(r.req)
            by_req[id(r.req)] = r
            waiting.append(r)
            nxt += 1
        if "t0" not in snaps and now >= t0:
            snap("t0")
            c_win = compiles.n
        if prof is not None and prof.t0 is None and now >= tr_at:
            prof.start()
            snap("tr0")
        if prof is not None and prof.t0 is not None and prof.t1 is None \
                and now >= prof.t0 + mix["trace"]["seconds"]:
            snap("tr1")
            prof.stop()
        if "t1" not in snaps and now >= t1:
            snap("t1")
            c_win = compiles.n - c_win
            backlog = sum(1 for r in waiting if r.due < t1)
        if now >= t1 and (seen() or now >= deadline):
            break
        if eng.pending():
            ts = time.perf_counter()
            rows0 = eng.stats.get("compact_prefill_rows", 0)
            svc.tick()
            most_rows = max(most_rows, eng.stats.get(
                "compact_prefill_rows", 0) - rows0)
            ticks.append((ts, time.perf_counter()))
            k = len(ticks) - 1
            for r in list(waiting):
                if r.req.first_token_t > 0 or r.req.status != "ok":
                    r.admit_tick = k
                    waiting.remove(r)
            for q in eng.drain_done():
                by_req[id(q)].finish_tick = k
            if t0 <= ts < t1:
                _, held, promised = pool_pages(eng)
                mem["pages_held_max"] = max(mem["pages_held_max"], held)
                mem["pages_promised_max"] = max(mem["pages_promised_max"],
                                                promised)
        else:
            gap = (recs[nxt].due if nxt < len(recs) else now + 1e-3) - now
            time.sleep(min(max(gap, 0.0), 0.002))
    if prof is not None and prof.t1 is None and prof.t0 is not None:
        snap("tr1")
        prof.stop()
    eng._sample = engine_sample
    pick = sample(recs, kept, mix, seed)
    return {"recs": recs, "measured": measured,
            "kept": {i: kept[i] for i in pick}, "retained": len(kept),
            "retained_unfinished": sum(1 for i in kept
                                       if recs[i].finish_tick is None),
            "ticks": ticks, "snaps": snaps, "late": late,
            "compiles": c_win, "prof": prof, "backlog": backlog,
            "prefill_rows_max": most_rows,
            "memory": mem}


def drain(svc):
    """Finish whatever the engine still holds."""
    while svc.serving.pending():
        svc.tick()
    svc.serving.drain_done()


def run(run):
    import jax

    from bench import harness
    from bench.stats import percentile
    mix = run.traffic
    svc, obs, compiles, shapes = setup(run)
    setup_peak = harness.peak_bytes(run.devices)
    t_first = time.perf_counter()
    run.metrics["setup_s"] = t_first - run.t_start
    pauses = harness.GcPauses()
    w = window(run, svc, obs, compiles, mix, run.seed, run.seconds, t_first,
               traced=run.traced)
    run.note("gc_pauses", pauses.stop())
    run.note("compiles_in_window", w["compiles"])
    run.note("generator_lateness_s", {
        "median": float(np.median(w["late"])),
        "max": float(np.max(w["late"])), "requests": len(w["late"])})
    run.memory_peak = harness.peak_bytes(run.devices)
    run.memory = dict(w["memory"], setup_peak_bytes=setup_peak,
                      in_use_after_window_bytes=harness.in_use_bytes(
                          run.devices))
    run.note("memory", run.memory)
    if w["prof"] is not None:
        run.trace = w["prof"].reduce()

    # -- end-to-end metrics over the measured set --------------------------
    measured = w["measured"]
    run.attempted = len(measured)
    ok = [r for r in measured if r.finish_tick is not None
          and r.req.status == "ok"]
    run.failed = sum(1 for r in measured if r.req is not None
                     and r.req.status != "ok")
    if below_knee(mix):
        run.failed = run.attempted - len(ok)
        ttft = [math.inf] * run.failed + [r.req.first_token_t - r.due
                                          for r in ok]
        tpot = [math.inf] * run.failed + [
            (r.req.finish_t - r.req.first_token_t) / (r.n - 1) for r in ok
            if r.n > 1]
        for q in (90, 95, 99):
            run.metrics[f"ttft_p{q}_ms"] = _finite(percentile(ttft, q) * 1e3)
            run.metrics[f"tpot_p{q}_ms"] = _finite(percentile(tpot, q) * 1e3)
    a, b = w["snaps"]["t0"], w["snaps"]["t1"]
    toks = (b[2]["decode_tokens"] - a[2]["decode_tokens"]
            + b[2]["admitted"] - a[2]["admitted"])
    run.metrics["serve_tokens_per_s"] = toks / (b[0] - a[0])
    run.note("window", {"requests": run.attempted, "failed": run.failed,
                        "finished": len(ok), "queued_at_close": w["backlog"],
                        "completed_in_window": sum(
                            1 for r in w["recs"] if r.finish_tick is not None
                            and a[0] <= r.req.finish_t < b[0]),
                        "prefill_rows_max": w["prefill_rows_max"],
                        "ticks": b[1] - a[1], "tokens": toks,
                        "seconds": b[0] - a[0],
                        "retained": w["retained"]})
    run.view = _view(run, w, shapes)

    # -- correctness: the reference scores a sample of served tokens -------
    del svc, obs
    gc.collect()
    jax.clear_caches()
    score(run, w["recs"], w["kept"])
    fine = run.check("retained_unfinished", w["retained_unfinished"], 0)
    run.correct = run.correct and fine and run.failed == 0


def _finite(x: float) -> float:
    """A tail that lands on a failed request has no finite value; an hour
    stands for it in the result line."""
    return x if math.isfinite(x) else 3.6e6


def _view(run, w, shapes):
    from types import SimpleNamespace

    from bench import harness
    return SimpleNamespace(
        cfg=run.config["model"], family=run.family, mix=run.traffic,
        recs=w["recs"],
        measured=w["measured"], ticks=w["ticks"], snaps=w["snaps"],
        trace=run.trace, peaks=harness.peaks(run), shapes=shapes)


def sample(recs, kept, mix: dict, seed: int) -> set:
    """Indices of the requests the reference scores, drawn from the seed
    among the retained requests that finished: the one with the most output
    tokens, one of each bank, then more at random until ``check.tokens``
    output tokens are in. The run retains every request whose first token
    comes once the window has opened (above the knee: while it is open),
    until ``check.retain_tokens`` output tokens are held, so the sample is
    drawn from finished requests without keeping the logits of the whole
    run."""
    done = [recs[i] for i in sorted(kept) if recs[i].finish_tick is not None
            and recs[i].req.status == "ok"]
    if not done:
        return set()
    rng = np.random.default_rng([int(seed) % (1 << 63), 3])
    owner = {c: b for c, b, _ in clients_of(mix)}
    longest = [r for r in done if r.n == max(q.n for q in done)]
    pick = [longest[rng.integers(len(longest))]]
    by_bank = {}
    for r in done:
        by_bank.setdefault(owner[r.client], []).append(r)
    for b in sorted(by_bank):
        if not any(owner[p.client] == b for p in pick):
            pick.append(by_bank[b][rng.integers(len(by_bank[b]))])
    rest = [r for r in done if r not in pick]
    for j in rng.permutation(len(rest)):
        if sum(p.n for p in pick) >= mix["check"]["tokens"]:
            break
        pick.append(rest[j])
    return {p.i for p in pick}


def readings(run, recs, kept, lowp: str = "") -> dict:
    """Over the kept requests that finished: the widest gap by which a
    served token's reference logit lies below the reference's best
    (``gap``), and the largest error of the program's logits relative to
    the reference's largest logit, per position (``error``). With ``lowp``,
    the same two numbers for the reference computed in that lower precision
    in the program's place (``control.*``): the gap of the token it puts
    first, and its logits' error. ``fault.gap`` reads the planted fault of
    a token altered where it is produced (each served token moved to the
    next id) against the same reference logits."""
    from bench.reference import ServeReference, served_gaps
    mix, md = run.traffic, run.config["model"]
    where = {c: (b, i) for c, b, i in clients_of(mix)}
    ref = ServeReference(md, run.seed, mix["banks"], family=run.family)
    low = ServeReference(md, run.seed, mix["banks"], lowp=lowp,
                         base=ref.base, family=run.family) if lowp else None
    out = {"gap": 0.0, "error": 0.0, "fault.gap": 0.0, "requests": 0,
           "tokens": 0}
    if low is not None:
        out.update({"control.gap": 0.0, "control.error": 0.0})

    def err(a, lg):
        return float(np.max(np.max(np.abs(a - lg), -1)
                            / np.max(np.abs(lg), -1)))

    for r in recs:
        if r.i not in kept or r.finish_tick is None or r.req.status != "ok":
            continue
        b, i = where[r.client]
        gen = np.asarray(r.req.generated[0])
        toks = np.concatenate([r.req.prompt[0], gen[:-1]])
        name = mix["banks"][b]["name"]
        lg = np.asarray(ref.logits(toks, len(gen), name, i))
        out["gap"] = max(out["gap"], float(np.max(served_gaps(lg, gen))))
        out["fault.gap"] = max(out["fault.gap"], float(np.max(
            served_gaps(lg, (gen + 1) % lg.shape[-1]))))
        out["error"] = max(out["error"], err(np.stack(kept[r.i]), lg))
        if low is not None:
            lo = np.asarray(low.logits(toks, len(gen), name, i))
            out["control.gap"] = max(out["control.gap"], float(np.max(
                served_gaps(lg, np.argmax(lo, axis=-1)))))
            out["control.error"] = max(out["control.error"], err(lo, lg))
        out["requests"] += 1
        out["tokens"] += len(gen)
    return out


def score(run, recs, kept):
    """Correct when both numbers are within the mix's limits over the kept
    requests and these hold the served tokens the check asks for."""
    mix = run.traffic
    t = time.perf_counter()
    got = readings(run, recs, kept)
    run.note("reference", {"requests": got["requests"],
                           "tokens": got["tokens"],
                           "seconds": time.perf_counter() - t})
    lim = mix["check"]
    ok = [run.check("max_logit_gap", got["gap"], lim["max_gap"]),
          run.check("max_logit_error", got["error"], lim["max_error"])]
    short = max(0, lim["tokens"] - got["tokens"])
    run.check("reference_tokens_short", short, 0)
    run.correct = all(ok) and got["requests"] > 0 and short == 0
"""Tick-level telemetry (docs/observability.md).

The contracts under test, in order of importance:

1. **Additive**: running an engine with ``obs=Obs()`` changes NOTHING about
   its outputs — serving token streams and fine-tuning trajectories are
   bitwise identical obs-on vs obs-off, and the autouse trace guard
   (conftest) proves telemetry introduces no new jit compiles.
2. **Free when off**: ``obs=None`` must not import ``repro.obs`` at all,
   and the null span is one shared context manager (no per-phase
   allocation, bounded wall-time overhead).
3. The metric/event/export primitives themselves: log-2 histogram bucket
   math and percentiles, filtered destructive event drains, JSONL and
   Prometheus exports accepted by the ``--check`` validator (and rejected
   once truncated).
"""
import json
import subprocess
import sys
import time
import warnings

import jax
import numpy as np
import pytest

from repro.config import AdapterConfig, FinetuneConfig, ServeConfig
from repro.core import symbiosis
from repro.faults.plan import FaultyRequestStream
from repro.obs import Obs
from repro.obs.events import EventLog
from repro.obs.metrics import Histogram, Metrics
from repro.obs import export
from repro.serving.engine import Request, ServingEngine
from repro.training import FinetuneEngine, FinetuneJob, make_job_stream
from conftest import tiny

LORA = AdapterConfig(method="lora", rank=4, alpha=8.0, targets=("q", "v"))


def _serving(cfg, base, bank, **kw):
    scfg = ServeConfig(n_clients=2, max_seq=32, page_block=8, pool_pages=8)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return ServingEngine(cfg, LORA, scfg, base, bank,
                             max_batch_per_client=2, debug=True, **kw)


def _prompts(cfg, per_client=2, seed=0):
    rng = np.random.default_rng(seed)
    return [[rng.integers(1, cfg.vocab, (1, 6)).astype(np.int32)
             for _ in range(per_client)] for _ in range(2)]


def _submit_all(eng, prompts, max_new=3):
    for c, ps in enumerate(prompts):
        for p in ps:
            eng.submit(Request(client_id=c, prompt=p.copy(),
                               max_new_tokens=max_new, arrive_tick=0))


def _job(cfg, i, steps=3):
    return FinetuneJob(acfg=LORA, data=make_job_stream(cfg, 2, 8, seed=i),
                       batch_size=2, seq_len=8, steps=steps, seed=i,
                       name=f"j{i}")


# ---------------------------------------------------------------------------
# metric primitives
# ---------------------------------------------------------------------------

def test_histogram_bucket_math_and_percentiles():
    h = Histogram()
    for _ in range(99):
        h.observe(1e-3)
    h.observe(0.1)
    # 1e-3 lands in bucket ceil(log2(1e-3/1e-6)) = 10, upper edge 1.024e-3
    assert h.counts[10] == 99
    assert h.percentile(50) == pytest.approx(1.024e-3)
    assert h.percentile(99) == pytest.approx(1.024e-3)
    # p100's bucket edge (0.131...) is clamped to the exact observed max
    assert h.percentile(100) == pytest.approx(0.1)
    assert h.n == 100 and h.vmin == 1e-3 and h.vmax == 0.1
    assert h.mean == pytest.approx((99 * 1e-3 + 0.1) / 100)
    # bucket 0 catches sub-resolution values
    h2 = Histogram()
    h2.observe(0.0)
    h2.observe(1e-7)
    assert h2.counts[0] == 2
    # merge is additive
    h.merge(h2)
    assert h.n == 102 and h.counts[0] == 2


def test_metrics_registry_labels_and_samples():
    m = Metrics()
    m.counter("tok", client=0).inc(5)
    m.counter("tok", client=1).inc(7)
    assert m.counter("tok", client=0).value == 5          # get-or-create
    m.gauge("free").set(3)
    m.histogram("lat", phase="a").observe(2e-3)
    merged = m.merged_histogram("lat")
    assert merged.n == 1
    rows = m.samples()
    names = [(r["metric"], r["type"]) for r in rows]
    assert names == sorted(names)                         # deterministic
    hist_row = next(r for r in rows if r["type"] == "histogram")
    assert hist_row["count"] == 1 and "p99" in hist_row


def test_event_log_filtered_drain_and_cap():
    log = EventLog(maxlen=4)
    for i in range(3):
        log.emit("admit", engine="serving", tick=i, tenant=i % 2)
    log.emit("retire", engine="serving", tick=9, tenant=0)
    seqs = [e.seq for e in log.peek()]
    assert len(set(seqs)) == 4 and seqs == sorted(seqs)
    mine = log.drain(tenant=0)
    assert {e.kind for e in mine} == {"admit", "retire"}
    assert all(e.tenant == 0 for e in mine)
    left = log.peek()                                      # others untouched
    assert all(e.tenant == 1 for e in left) and len(left) == 1
    # cap: overflow bumps the dropped counter instead of growing
    for i in range(10):
        log.emit("admit", engine="serving", tick=i)
    assert len(log.peek()) == 4 and log.dropped > 0


def test_span_label_built_once_and_tick_log():
    from repro.obs.trace import TickLog
    obs = Obs()
    sp = obs.span("prefill")
    assert sp._label == "repro.obs/prefill" and obs.span("prefill") is sp
    t0 = obs.tick_start("serving")
    with obs.span("prefill"):
        with obs.span("prefill_sync"):     # nested: listed, counted once
            time.sleep(0.002)
    with obs.span("sample"):
        pass
    time.sleep(0.003)                       # in no phase
    obs.tick_end("serving", 1, t0)
    (rec,) = obs.ticks.records
    ph = rec["phases"]
    assert set(ph) == {"prefill", "prefill_sync", "sample"}
    assert ph["prefill"] >= ph["prefill_sync"] >= 0.002
    assert rec["uncovered"] >= 0.003
    assert rec["seconds"] == pytest.approx(
        ph["prefill"] + ph["sample"] + rec["uncovered"], abs=1e-9)
    assert rec["engine"] == "serving" and rec["start"] == t0
    # the longest ticks of a window, of one engine, longest first
    log = TickLog(maxlen=3)
    for i, (eng, t, secs) in enumerate([("serving", 1.0, 0.5),
                                        ("finetune", 2.0, 3.0),
                                        ("serving", 3.0, 0.9),
                                        ("serving", 4.0, 0.1)]):
        log.start()
        log.end(eng, i, t, t + secs)
    assert len(log.records) == 3                    # the oldest dropped
    assert [r["tick"] for r in log.longest(2)] == [1, 2]
    assert [r["tick"] for r in log.longest(5, engine="serving")] == [2, 3]
    assert [r["tick"] for r in log.longest(5, since=2.5, until=4.0)] == [2]


# ---------------------------------------------------------------------------
# contract 1: telemetry is bitwise-invisible (trace guard via conftest)
# ---------------------------------------------------------------------------

def test_obs_on_off_bitwise_serving(key):
    cfg = tiny()
    base, bank, _ = symbiosis.init_system(cfg, LORA, 2, key)
    prompts = _prompts(cfg)
    off = _serving(cfg, base, bank)
    on = _serving(cfg, base, bank, obs=Obs())
    _submit_all(off, prompts)
    _submit_all(on, prompts)
    ref = {r.prompt.tobytes(): r.generated for r in off.run()}
    done = on.run()
    assert len(done) == len(ref)
    for r in done:
        assert r.status == "ok"
        np.testing.assert_array_equal(r.generated, ref[r.prompt.tobytes()])
    # the compatibility view is untouched by the mirror
    assert on.stats["ticks"] == off.stats["ticks"]


def test_obs_on_off_bitwise_finetune(key):
    cfg = tiny()
    base, _, _ = symbiosis.init_system(cfg, LORA, 2, key)
    results = {}
    for tag, obs in (("off", None), ("on", Obs())):
        eng = FinetuneEngine(cfg, base, fcfg=FinetuneConfig(max_jobs=2),
                             debug=True, obs=obs)
        jobs = [_job(cfg, 0), _job(cfg, 1)]
        for j in jobs:
            eng.submit(j)
        eng.run()
        results[tag] = jobs
    for a, b in zip(results["off"], results["on"]):
        np.testing.assert_array_equal(a.losses, b.losses)
        for x, y in zip(jax.tree.leaves((a.result.adapter, a.result.opt)),
                        jax.tree.leaves((b.result.adapter, b.result.opt))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_serving_metrics_and_latency_fields(key):
    cfg = tiny()
    base, bank, _ = symbiosis.init_system(cfg, LORA, 2, key)
    obs = Obs()
    eng = _serving(cfg, base, bank, obs=obs)
    _submit_all(eng, _prompts(cfg))
    done = eng.run()
    # satellite: submit_t/finish_t are now surfaced as per-request latency
    for r in done:
        assert r.queue_wait is not None and r.queue_wait >= 0
        assert r.ttft is not None and r.ttft >= r.queue_wait
        assert r.e2e_latency is not None and r.e2e_latency >= r.ttft
    m = obs.metrics
    assert m.merged_histogram("serve_queue_wait_seconds").n == len(done)
    assert m.merged_histogram("serve_ttft_seconds").n == len(done)
    assert m.merged_histogram("serve_e2e_seconds").n == len(done)
    toks = sum(r.generated.size for r in done)
    decode = sum(m.counter("serve_decode_tokens_total", client=c).value
                 for c in (0, 1))
    prefill = sum(m.counter("serve_prefill_tokens_total", client=c).value
                  for c in (0, 1))
    assert decode + 0 == sum(max(r.generated.size - 1, 0) for r in done)
    assert prefill == sum(r.prompt.size for r in done)
    assert toks > 0
    # per-phase spans observed real time
    spans = m.merged_histogram("span_seconds")
    assert spans.n > 0
    assert m.merged_histogram("tick_seconds").n == eng.stats["ticks"]
    # the stats dict is mirrored as gauges at snapshot time
    snap = obs.snapshot()
    stat_rows = [r for r in snap["metrics"] if r["metric"] == "engine_stat"]
    assert {r["labels"]["key"] for r in stat_rows} >= set(eng.stats)


def test_latency_fields_without_obs(key):
    """The Request latency timeline works with telemetry detached — the
    timestamps are engine bookkeeping, not an obs feature."""
    cfg = tiny()
    base, bank, _ = symbiosis.init_system(cfg, LORA, 2, key)
    eng = _serving(cfg, base, bank)
    _submit_all(eng, _prompts(cfg, per_client=1))
    done = eng.run()
    assert all(r.e2e_latency is not None for r in done)
    assert eng.drain_events() == []


def test_finetune_metrics_and_events(key):
    cfg = tiny()
    base, _, _ = symbiosis.init_system(cfg, LORA, 2, key)
    obs = Obs()
    eng = FinetuneEngine(cfg, base, fcfg=FinetuneConfig(max_jobs=2),
                         debug=True, obs=obs)
    jobs = [_job(cfg, 0), _job(cfg, 1)]
    for j in jobs:
        eng.submit(j)
    eng.run()
    for j in jobs:
        assert obs.metrics.counter(
            "train_steps_total", job=j.name).value == j.steps
        assert j.fault_history == []
    ev = eng.drain_events()
    kinds = [e.kind for e in ev]
    assert kinds.count("admit") == 2 and kinds.count("retire") == 2
    admits = [e for e in ev if e.kind == "admit"]
    assert {e.tenant for e in admits} == {"j0", "j1"}
    # drained means drained
    assert eng.drain_events() == []


# ---------------------------------------------------------------------------
# contract 2: disabled mode is free
# ---------------------------------------------------------------------------

def test_engines_do_not_import_obs_when_disabled():
    """The hard constraint from docs/observability.md: with obs=None no
    timing machinery is even imported — the engines must be importable and
    runnable without repro.obs ever entering sys.modules."""
    code = (
        "import sys\n"
        "import repro.serving.engine, repro.training.engine\n"
        "import repro.training.service\n"
        "assert not any(m.startswith('repro.obs') for m in sys.modules), "
        "sorted(m for m in sys.modules if m.startswith('repro.obs'))\n"
        "print('clean')\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={"PYTHONPATH": "src",
                                         "JAX_PLATFORMS": "cpu",
                                         "PATH": "/usr/bin:/bin"},
                         cwd=".")
    assert out.returncode == 0, out.stderr
    assert "clean" in out.stdout


def test_disabled_span_is_shared_and_cheap(key):
    from repro.serving.engine import _NULL_CTX, _null_span
    # one shared nullcontext: no allocation per phase per tick
    assert _null_span("admit") is _NULL_CTX
    assert _null_span("jit_dispatch") is _NULL_CTX
    cfg = tiny()
    base, bank, _ = symbiosis.init_system(cfg, LORA, 2, key)
    eng = _serving(cfg, base, bank)
    assert eng._span is _null_span and eng._obs is None
    # bounded wall-time: 100k disabled span cycles must be cheap relative
    # to a bare loop (generous 50x/0.5s bound — this is pure-python ctx
    # entry, far below one engine tick)
    N = 100_000
    t0 = time.perf_counter()
    for _ in range(N):
        pass
    bare = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(N):
        with _null_span("x"):
            pass
    spans = time.perf_counter() - t0
    assert spans < max(50 * bare, 0.5), (spans, bare)


# ---------------------------------------------------------------------------
# events under churn + stream faults through the client-visible feed
# ---------------------------------------------------------------------------

def test_drain_events_under_churn(key):
    cfg = tiny()
    base, bank, _ = symbiosis.init_system(cfg, LORA, 2, key)
    obs = Obs()
    eng = _serving(cfg, base, bank, obs=obs)
    _submit_all(eng, _prompts(cfg, per_client=3))
    eng.run()
    c0 = eng.drain_events(client=0)
    c1 = eng.drain_events(client=1)
    assert c0 and c1
    assert all(e.tenant == 0 for e in c0)
    assert all(e.tenant == 1 for e in c1)
    seqs = [e.seq for e in c0 + c1]
    assert len(seqs) == len(set(seqs))
    assert {e.kind for e in c0} >= {"admit", "retire"}
    # kind-filtered drain of what's left (tenant-less events like compile)
    rest = eng.drain_events()
    assert all(e.tenant is None for e in rest)
    assert eng.drain_events() == []


def test_serving_stream_fault_retry_bitwise_and_events(key):
    """A transient request-stream error backs the client off; the retried
    fetch draws the SAME prompt so the stream is bitwise identical — and
    the whole episode is visible as backoff/retry events plus the
    request's fault_history."""
    cfg = tiny()
    base, bank, _ = symbiosis.init_system(cfg, LORA, 2, key)
    prompts = _prompts(cfg, per_client=1)
    clean = _serving(cfg, base, bank)
    _submit_all(clean, prompts)
    ref = {r.prompt.tobytes(): r.generated for r in clean.run()}

    obs = Obs()
    eng = _serving(cfg, base, bank, obs=obs)
    stream = FaultyRequestStream(prompts[0][0], {0: "stream_error"})
    eng.submit(Request(client_id=0, prompt=None, prompt_stream=stream,
                       max_new_tokens=3, arrive_tick=0))
    eng.submit(Request(client_id=1, prompt=prompts[1][0].copy(),
                       max_new_tokens=3, arrive_tick=0))
    done = eng.run()
    assert stream.calls == 2                    # faulted + successful retry
    assert all(r.status == "ok" for r in done)
    for r in done:
        np.testing.assert_array_equal(r.generated, ref[r.prompt.tobytes()])
    victim = next(r for r in done if r.client_id == 0)
    assert [k for _, k, _ in victim.fault_history] == ["backoff"]
    ev = eng.drain_events(client=0)
    kinds = [e.kind for e in ev]
    assert "backoff" in kinds and "retry" in kinds and "admit" in kinds


def test_serving_stream_end_rejects_with_event(key):
    cfg = tiny()
    base, bank, _ = symbiosis.init_system(cfg, LORA, 2, key)
    prompts = _prompts(cfg, per_client=1)
    obs = Obs()
    eng = _serving(cfg, base, bank, obs=obs)
    stream = FaultyRequestStream(prompts[0][0], {0: "stream_end"})
    eng.submit(Request(client_id=0, prompt=None, prompt_stream=stream,
                       max_new_tokens=3, arrive_tick=0))
    eng.submit(Request(client_id=1, prompt=prompts[1][0].copy(),
                       max_new_tokens=3, arrive_tick=0))
    done = eng.run()
    by_client = {r.client_id: r for r in done}
    assert by_client[0].status == "rejected"
    assert by_client[0].generated is None or by_client[0].generated.size == 0
    assert [k for _, k, _ in by_client[0].fault_history] == ["rejected"]
    assert by_client[1].status == "ok"
    kinds = {e.kind for e in eng.drain_events(client=0)}
    assert "reject" in kinds and "admit" not in kinds


def test_symbiosis_shared_obs_merged_feed(key):
    from repro.core.engine_spec import BankSpec, EngineSpec
    from repro.training.service import SymbiosisEngine
    cfg = tiny()
    base, bank, _ = symbiosis.init_system(cfg, LORA, 2, key)
    scfg = ServeConfig(n_clients=2, max_seq=32)
    spec = EngineSpec(cfg=cfg, banks=(BankSpec("b", LORA, capacity=2),),
                      serve=scfg, finetune=FinetuneConfig(max_jobs=1),
                      max_batch_per_client=2)
    obs = Obs()
    sym = SymbiosisEngine.from_spec(spec, base, serving_banks=[bank],
                                    obs=obs)
    prompts = _prompts(cfg, per_client=1)
    sym.submit(Request(client_id=0, prompt=prompts[0][0].copy(),
                       max_new_tokens=3, arrive_tick=0))
    sym.submit(_job(cfg, 0, steps=2))
    sym.run()
    ev = sym.drain_events()
    engines = {e.engine for e in ev}
    assert "serving" in engines and "finetune" in engines
    seqs = [e.seq for e in ev]
    assert seqs == sorted(seqs)
    assert sym.drain_events() == []


# ---------------------------------------------------------------------------
# exports + validator
# ---------------------------------------------------------------------------

def _small_obs():
    obs = Obs()
    obs.metrics.counter("serve_decode_tokens_total", client=0).inc(12)
    obs.metrics.gauge("serve_pages_free", client=0).set(5)
    h = obs.metrics.histogram("serve_ttft_seconds", client=0)
    h.observe(1e-3)
    h.observe(2e-3)
    obs.event("admit", engine="serving", tick=0, tenant=0, rows=1)
    obs.event("retire", engine="serving", tick=3, tenant=0, status="ok")
    return obs


def test_jsonl_export_golden_and_check(tmp_path):
    obs = _small_obs()
    path = str(tmp_path / "t.jsonl")
    export.write_jsonl(path, obs)
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["record"] == "header" and lines[0]["schema"] == 1
    assert lines[-1]["record"] == "footer"
    assert lines[-1]["n"] == len(lines) - 2
    kinds = {l.get("record") for l in lines[1:-1]}
    assert kinds == {"metric", "event"}
    hist = next(l for l in lines if l.get("type") == "histogram")
    assert hist["count"] == 2 and hist["buckets"]
    assert export.check_file(path) == []
    # truncation (lost footer) must be rejected
    with open(path) as f:
        full = f.readlines()
    with open(path, "w") as f:
        f.writelines(full[:-1])
    assert export.check_file(path)


def test_prometheus_export_golden_and_check(tmp_path):
    obs = _small_obs()
    path = str(tmp_path / "t.prom")
    export.write_prometheus(path, obs)
    text = open(path).read()
    assert text.rstrip().endswith("# EOF")
    assert 'serve_decode_tokens_total{client="0"} 12' in text
    # cumulative histogram framing with +Inf and _count
    assert 'serve_ttft_seconds_bucket{client="0",le="+Inf"} 2' in text
    assert 'serve_ttft_seconds_count{client="0"} 2' in text
    assert export.check_file(path) == []
    with open(path, "w") as f:
        f.write(text.replace("# EOF", ""))
    assert export.check_file(path)


def test_check_cli_exit_codes(tmp_path):
    from repro.obs.__main__ import main
    obs = _small_obs()
    good = str(tmp_path / "ok.jsonl")
    export.write_jsonl(good, obs)
    assert main(["--check", good]) == 0
    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write('{"record": "metric"}\n')       # no header/footer framing
    assert main(["--check", bad]) != 0
    assert main(["--check", good, bad]) != 0    # one bad file fails the set


# ---------------------------------------------------------------------------
# profiler capture window
# ---------------------------------------------------------------------------

def test_capture_window_smoke(key, tmp_path):
    cfg = tiny()
    base, bank, _ = symbiosis.init_system(cfg, LORA, 2, key)
    obs = Obs()
    eng = _serving(cfg, base, bank, obs=obs)
    obs.request_capture(str(tmp_path / "prof"), ticks=1)
    _submit_all(eng, _prompts(cfg, per_client=1))
    eng.run()
    kinds = [e.kind for e in obs.events.peek()]
    if "capture_failed" in kinds:               # profiler unavailable here
        assert "capture_start" not in kinds
    else:
        assert "capture_start" in kinds and "capture_stop" in kinds

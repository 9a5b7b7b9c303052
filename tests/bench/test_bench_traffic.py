"""The open-loop generator: determinism by seed, the same multiset of
sizes for every seed, due-time bookkeeping and lateness, submission when
due, and the sample of retained requests below and above the knee, driven
against a stand-in engine that needs no model."""
import time
from types import SimpleNamespace

import numpy as np

from benchtiny import CHAT

from bench.traffic import open_loop as ol


def test_schedule_is_the_same_for_every_seed():
    mix = dict(CHAT, ramp_s=3.0)
    a, b = ol.schedule(mix, 20.0), ol.schedule(mix, 20.0)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    win = (a["due"] >= 3.0) & (a["due"] < 23.0)
    assert win.sum() == round(6.0 * 20.0)
    assert a["due"][0] == 0 and np.all(np.diff(a["due"]) >= 0)
    assert len(a["due"]) == round(6.0 * 3) + round(6.0 * 20) + round(
        6.0 * CHAT["drain_s"])
    # the seed draws the tokens
    np.testing.assert_array_equal(ol.prompt_tokens(3, 5, 10, 256),
                                  ol.prompt_tokens(3, 5, 10, 256))
    assert not np.array_equal(ol.prompt_tokens(3, 5, 10, 256),
                              ol.prompt_tokens(2**31 + 3, 5, 10, 256))


def test_sizes_and_popularity():
    s = ol.schedule(dict(CHAT, drain_s=0), 400 / 6.0)
    assert s["prompt_len"].min() >= CHAT["prompt"]["min"]
    assert s["prompt_len"].max() <= CHAT["prompt"]["max"]
    assert s["out_len"].min() >= CHAT["output"]["min"]
    counts = np.bincount(s["client"], minlength=6)
    order = ol.zipf_order(CHAT)
    assert order == [0, 2, 4, 1, 3, 5]
    # Zipf 1.0 over 6 clients: the top client holds 1/H6 = 40.8%
    assert abs(counts[order[0]] / 400 - 1 / sum(1 / k for k in range(1, 7))) \
        < 0.01
    assert list(counts[order]) == sorted(counts, reverse=True)


class _Engine:
    """Admits, on the next tick, what it was handed while fewer than ``cap``
    requests are live, samples the first token there, then one token per
    tick; ``tick_s`` of sleep per tick."""

    def __init__(self, tick_s, cap):
        self.tick_s, self.cap = tick_s, cap
        self.queue, self.live, self.done = [], [], []
        self.stats = {"decode_tokens": 0, "admitted": 0, "ticks": 0,
                      "compact_rows": 0}
        self.max_queue = 0

    def pending(self):
        return bool(self.queue or self.live)

    def _sample(self, logits, req):
        return np.zeros((1,), np.int32)

    def drain_done(self):
        d, self.done = self.done, []
        return d


class _Service:
    def __init__(self, tick_s=0.004, cap=10**9):
        self.serving = _Engine(tick_s, cap)

    def submit(self, req):
        e = self.serving
        req.submit_t = time.perf_counter()
        e.queue.append(req)
        e.max_queue = max(e.max_queue, len(e.queue))

    def tick(self):
        e = self.serving
        time.sleep(e.tick_s)
        now = time.perf_counter()
        lg = np.zeros((1, 8), np.float32)
        while e.queue and len(e.live) < e.cap:
            r = e.queue.pop(0)
            r.admit_t = r.first_token_t = now
            r.left = r.max_new_tokens - 1
            r.generated = np.zeros((1, r.max_new_tokens), np.int32)
            e._sample(lg, r)
            e.stats["admitted"] += 1
            e.live.append(r)
        for r in list(e.live):
            if r.left > 0:
                r.left -= 1
                e._sample(lg, r)
                e.stats["decode_tokens"] += 1
            if r.left == 0:
                r.finish_t = now
                e.live.remove(r)
                e.done.append(r)
        e.stats["ticks"] += 1


def _window(mix, svc, seconds):
    run = SimpleNamespace(config={"model": {"vocab": 256}},
                          root="/nonexistent")
    counter = SimpleNamespace(n=0)
    import repro.serving.engine  # noqa: F401  imported by set-up in a run
    t_first = time.perf_counter()
    return t_first, ol.window(run, svc, None, counter, mix, 9, seconds,
                              t_first)


def test_window_bookkeeping():
    mix = dict(CHAT, arrivals={"process": "poisson", "cv": 1.0,
                               "rate_rps": 80.0}, drain_s=5)
    svc = _Service()
    t_first, w = _window(mix, svc, 0.5)
    m = w["measured"]
    t0 = w["snaps"]["t0"][0]
    assert m and all(t_first <= r.due < t_first + 0.5 for r in m)
    assert len(m) == sum(1 for r in w["recs"] if t_first <= r.due
                         < t_first + 0.5)
    assert all(r.finish_tick is not None and r.req.status == "ok" for r in m)
    for r in m:
        # every latency counts from the due time, which the generator
        # never hands on early
        assert r.req.submit_t >= r.due
        assert r.req.first_token_t - r.due >= r.req.first_token_t - \
            r.req.submit_t
        assert r.finish_tick - r.admit_tick == max(r.n - 2, 0)
    assert len(w["late"]) >= len(m) and min(w["late"]) >= 0
    assert np.median(w["late"]) < 0.05
    assert t0 >= t_first
    assert w["compiles"] == 0
    # the sample: finished retained requests with the longest among them,
    # one logits row per served token
    assert w["kept"] and w["retained_unfinished"] == 0
    done = [w["recs"][i] for i in w["kept"]]
    assert all(len(w["kept"][r.i]) == r.n for r in done)
    assert sum(r.n for r in done) >= CHAT["check"]["tokens"]


def test_requests_go_to_the_engine_when_due():
    """No front end of the benchmark's own: a request is submitted when it
    is due, however many wait in the engine's queue."""
    mix = dict(CHAT, arrivals={"process": "poisson", "cv": 1.0,
                               "rate_rps": 400.0}, drain_s=0.2,
               load="above_knee")
    svc = _Service(cap=2)
    _, w = _window(mix, svc, 0.3)
    submitted = [r for r in w["recs"] if r.req is not None]
    assert svc.serving.max_queue > 8
    assert all(r.req.submit_t - r.due < 0.05 for r in submitted)


def test_above_the_knee_waits_only_for_the_retained():
    """Above the knee the queue grows all through the run: the window ends
    once the retained requests have finished, and requests still queued are
    late, not failed."""
    mix = dict(CHAT, arrivals={"process": "poisson", "cv": 1.0,
                               "rate_rps": 300.0}, drain_s=5,
               load="above_knee", ramp_s=0.1)
    svc = _Service(cap=2)
    t_first, w = _window(mix, svc, 0.4)
    m = w["measured"]
    assert any(r.finish_tick is None for r in m)
    assert w["retained"] > 0 and w["retained_unfinished"] == 0
    assert w["backlog"] > 0
    for i in w["kept"]:
        r = w["recs"][i]
        assert r.finish_tick is not None and len(w["kept"][i]) == r.n
        # retained: its first token came while the window was open
        assert t_first + 0.1 <= r.req.first_token_t < t_first + 0.5


def test_staged_batches_are_the_drawn_ones():
    from bench.traffic import finetune_jobs as fj
    s = fj.Stream(2**31 + 5, 1, 1, 8, 100)
    s.stage(2)
    for step in range(3):       # two staged, the third drawn when asked
        got, want = s.batch(step), s.numpy(step)
        for k in want:
            np.testing.assert_array_equal(np.asarray(got[k]), want[k])
    assert not s.staged

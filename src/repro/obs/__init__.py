"""repro.obs — tick-level telemetry for the symbiotic engines.

One ``Obs`` object bundles the three telemetry surfaces and is passed to the
engines as ``obs=`` (ServingEngine / FinetuneEngine / SymbiosisEngine.from_spec):

- ``obs.metrics``  — labeled counters/gauges/log-bucketed histograms
  (per-tenant tokens, pages, HBM charges, queue-wait, TTFT, inter-token
  latency; the engines' ``stats`` dicts are mirrored in as gauges at
  snapshot time, keeping ``stats`` as the compatibility view).
- ``obs.span(name)`` — reusable tick-phase spans emitting ``jax.profiler``
  named scopes plus per-phase latency histograms; ``obs.ticks`` keeps each
  tick's seconds by phase (``obs.ticks.longest`` finds the stalls).
- ``obs.events`` / ``obs.event(...)`` — the structured, drainable event log
  (client-visible via ``engine.drain_events(client=...)``).

Hard contracts (tested in tests/test_obs.py):

- ``obs=None`` (the default) is a hard no-op: the engines' tick loops see
  only ``if self._obs is not None`` guards and shared null context
  managers — no timing machinery is even imported on that path.
- Enabled telemetry adds **no device syncs inside the tick** (all
  timestamps are host ``perf_counter`` calls at tick/phase boundaries),
  **no new jit traces** (the autouse trace-guard stays green), and leaves
  engine outputs **bitwise unchanged**.

``obs.request_capture(log_dir, ticks=N)`` arms an on-demand profiler
capture window spanning the next N engine ticks.  Export via
``repro.obs.export`` (JSONL + Prometheus text) or the
``python -m repro.obs`` CLI; full schema in docs/observability.md.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

from repro.obs.events import UNSET, Event, EventLog
from repro.obs.metrics import Counter, Gauge, Histogram, Metrics
from repro.obs.trace import CaptureWindow, Span, TickLog

__all__ = [
    "Obs", "Metrics", "Counter", "Gauge", "Histogram",
    "Event", "EventLog", "Span", "TickLog", "CaptureWindow", "UNSET",
]


class Obs:
    """Telemetry facade shared by (possibly several) engines."""

    def __init__(self, *, max_events: int = 10000) -> None:
        self.metrics = Metrics()
        self.events = EventLog(maxlen=max_events)
        self.ticks = TickLog()
        self._spans: Dict[str, Span] = {}
        self._engines: Dict[str, object] = {}
        self._capture = CaptureWindow()
        self._compiled: set = set()

    # -- engine registration / stats compatibility view ------------------
    def attach(self, label: str, engine) -> str:
        """Register an engine so snapshots mirror its ``stats`` dict."""
        base, n = label, 1
        while label in self._engines and self._engines[label] is not engine:
            n += 1
            label = f"{base}_{n}"
        self._engines[label] = engine
        return label

    def sync_stats(self) -> None:
        """Mirror every attached engine's ``stats`` dict into gauges.

        ``stats`` stays the authoritative compatibility view (checkpointing
        round-trips it); the mirror makes the same numbers exportable under
        one metric name: ``engine_stat{engine=...,key=...}``.
        """
        for label, eng in self._engines.items():
            for k, v in getattr(eng, "stats", {}).items():
                self.metrics.gauge("engine_stat", engine=label, key=k).set(v)

    # -- spans / tick boundaries -----------------------------------------
    def span(self, name: str) -> Span:
        sp = self._spans.get(name)
        if sp is None:
            sp = self._spans[name] = Span(
                name, self.metrics.histogram("span_seconds", phase=name),
                self.ticks)
        return sp

    def tick_start(self, engine: str) -> float:
        kind = self._capture.on_tick_start()
        if kind is not None:
            self.event(kind, engine=engine, log_dir=self._capture.log_dir or "")
        self.ticks.start()
        return time.perf_counter()

    def tick_end(self, engine: str, tick: int, t0: float) -> None:
        t1 = time.perf_counter()
        self.metrics.histogram("tick_seconds", engine=engine).observe(t1 - t0)
        self.ticks.end(engine, tick, t0, t1)
        kind = self._capture.on_tick_end()
        if kind is not None:
            self.event(kind, engine=engine, tick=tick)

    def request_capture(self, log_dir: str, ticks: int = 1) -> None:
        """Arm a one-shot profiler capture for the next ``ticks`` engine ticks."""
        self._capture.request(log_dir, ticks)

    # -- events -----------------------------------------------------------
    def event(self, kind: str, **kw) -> Event:
        return self.events.emit(kind, **kw)

    def drain_events(self, *, client=UNSET, kind: Optional[str] = None,
                     engine: Optional[str] = None) -> List[Event]:
        """Destructive filtered drain (client= filters the tenant field)."""
        return self.events.drain(tenant=client, kind=kind, engine=engine)

    # -- tracecount hook ---------------------------------------------------
    def on_dispatch_compile(self, owner, family: str, key, epoch: int) -> None:
        """Called by ``tracecount.dispatch`` when a jitted hot-path function
        grew its cache.  First sighting of (owner, epoch, family, key) is a
        ``compile`` event; repeats are ``recompile`` — the signal the
        trace-guard turns into a hard failure in tests."""
        sig = (id(owner), epoch, family, repr(key))
        kind = "compile" if sig not in self._compiled else "recompile"
        self._compiled.add(sig)
        self.metrics.counter(f"jit_{kind}s_total", family=family).inc()
        # label the event with the engine's attach label ("serving" /
        # "finetune") so engine-filtered drains include compile events;
        # unattached owners fall back to their class name
        label = next((l for l, e in self._engines.items() if e is owner),
                     type(owner).__name__)
        self.event(kind, engine=label, family=family, key=repr(key))

    # -- snapshot ----------------------------------------------------------
    def snapshot(self) -> dict:
        self.sync_stats()
        return {
            "metrics": self.metrics.samples(),
            "events": [e.asdict() for e in self.events.peek()],
            "dropped_events": self.events.dropped,
        }

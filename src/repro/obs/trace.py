"""Tick-phase spans and the on-demand profiler capture window.

A ``Span`` is a reusable context manager for one named tick phase (the
taxonomy — ``admit``, ``prefill`` and the spans nested in it,
``compact_gather``, ``jit_dispatch``, ``device_sync``, ``sample``,
``scatter``, ``health_audit``, ``train_step`` — is documented in
docs/observability.md).  Entering a span opens a
``jax.profiler.TraceAnnotation`` named scope (a host-side TraceMe: it tags
profiler timelines but never blocks on the device) and records a
``perf_counter`` pair into a per-phase latency histogram on exit, and into
the current tick's record of its ``TickLog``.
Timestamps are taken only at phase boundaries — spans never call
``block_until_ready``, so whatever async dispatch the engine does is
unchanged.

``TickLog`` keeps, per engine tick, the seconds of each phase and the
seconds no phase covers, so a long tick can be put down to the phase it
spent its time in.

``CaptureWindow`` arms a one-shot ``jax.profiler.start_trace`` /
``stop_trace`` pair spanning the next N engine ticks.  Capture is
best-effort: profiler failures are reported as events, never raised into
the tick loop.
"""
from __future__ import annotations

import time
from collections import deque
from typing import List, Optional

from jax.profiler import TraceAnnotation


class TickLog:
    """Per engine tick, in memory: the engine, its tick number, its start
    on the host clock (``perf_counter``), its seconds, the seconds of each
    phase entered in it, and the seconds no phase covers. A phase nested in
    another is listed under its own name and counted once, in its parent,
    toward what the phases cover. The newest ``maxlen`` ticks are kept."""

    def __init__(self, maxlen: int = 100_000) -> None:
        self.records: deque = deque(maxlen=maxlen)
        self._phases: dict = {}
        self._covered = 0.0
        self._depth = 0

    def start(self) -> None:
        self._phases, self._covered, self._depth = {}, 0.0, 0

    def enter(self) -> None:
        self._depth += 1

    def exit(self, name: str, dt: float) -> None:
        self._depth -= 1
        self._phases[name] = self._phases.get(name, 0.0) + dt
        if self._depth == 0:
            self._covered += dt

    def end(self, engine: str, tick: int, t0: float, t1: float) -> None:
        self.records.append({
            "engine": engine, "tick": tick, "start": t0, "seconds": t1 - t0,
            "phases": self._phases,
            "uncovered": max(0.0, t1 - t0 - self._covered)})
        self.start()

    def longest(self, n: int = 3, since: float = -float("inf"),
                until: float = float("inf"),
                engine: Optional[str] = None) -> List[dict]:
        """The ``n`` longest ticks that started in ``[since, until)``
        (of one engine, if given), longest first."""
        ticks = [r for r in self.records if since <= r["start"] < until
                 and (engine is None or r["engine"] == engine)]
        return sorted(ticks, key=lambda r: -r["seconds"])[:n]


class Span:
    """Reusable single-threaded context manager for one tick phase."""

    __slots__ = ("name", "_label", "_hist", "_ticks", "_t0", "_ann")

    def __init__(self, name: str, hist, ticks: Optional[TickLog] = None
                 ) -> None:
        self.name = name
        self._label = f"repro.obs/{name}"   # the profiler scope's name
        self._hist = hist  # obs-owned Histogram for this phase
        self._ticks = ticks
        self._t0 = 0.0
        self._ann = None

    def __enter__(self) -> "Span":
        self._ann = TraceAnnotation(self._label)
        self._ann.__enter__()
        if self._ticks is not None:
            self._ticks.enter()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        self._ann = None
        self._hist.observe(dt)
        if self._ticks is not None:
            self._ticks.exit(self.name, dt)
        return False


class CaptureWindow:
    """One-shot profiler capture armed for the next N ticks.

    ``request`` arms; the owning ``Obs`` calls ``on_tick_start`` /
    ``on_tick_end`` from the engine tick boundaries.  Returns event kinds
    ("capture_start", "capture_stop", "capture_failed") so the caller can
    log them; None when nothing happened.
    """

    def __init__(self) -> None:
        self.log_dir: Optional[str] = None
        self.ticks_left = 0
        self.active = False

    def request(self, log_dir: str, ticks: int = 1) -> None:
        self.log_dir = str(log_dir)
        self.ticks_left = max(1, int(ticks))

    def on_tick_start(self) -> Optional[str]:
        if self.active or self.log_dir is None:
            return None
        try:
            import jax.profiler as _prof

            _prof.start_trace(self.log_dir)
        except Exception:
            self.log_dir = None
            self.ticks_left = 0
            return "capture_failed"
        self.active = True
        return "capture_start"

    def on_tick_end(self) -> Optional[str]:
        if not self.active:
            return None
        self.ticks_left -= 1
        if self.ticks_left > 0:
            return None
        self.active = False
        self.log_dir = None
        try:
            import jax.profiler as _prof

            _prof.stop_trace()
        except Exception:
            return "capture_failed"
        return "capture_stop"

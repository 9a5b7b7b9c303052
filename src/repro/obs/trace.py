"""Tick-phase spans and the on-demand profiler capture window.

A ``Span`` is a reusable context manager for one named tick phase (the
taxonomy — ``admit``, ``prefill``, ``compact_gather``, ``jit_dispatch``,
``device_sync``, ``scatter``, ``health_audit``, ``train_step`` — is
documented in docs/observability.md).  Entering a span opens a
``jax.profiler.TraceAnnotation`` named scope (a host-side TraceMe: it tags
profiler timelines but never blocks on the device) and records a
``perf_counter`` pair into a per-phase latency histogram on exit.
Timestamps are taken only at phase boundaries — spans never call
``block_until_ready``, so whatever async dispatch the engine does is
unchanged.

``CaptureWindow`` arms a one-shot ``jax.profiler.start_trace`` /
``stop_trace`` pair spanning the next N engine ticks.  Capture is
best-effort: profiler failures are reported as events, never raised into
the tick loop.
"""
from __future__ import annotations

import time
from typing import Optional

from jax.profiler import TraceAnnotation


class Span:
    """Reusable single-threaded context manager for one tick phase."""

    __slots__ = ("name", "_hist", "_t0", "_ann")

    def __init__(self, name: str, hist) -> None:
        self.name = name
        self._hist = hist  # obs-owned Histogram for this phase
        self._t0 = 0.0
        self._ann = None

    def __enter__(self) -> "Span":
        self._ann = TraceAnnotation(f"repro.obs/{self.name}")
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        self._ann = None
        self._hist.observe(dt)
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

"""Device time per program, from a profiler trace's ``XLA Modules`` line.

On a v5e the plane ``/device:TPU:0`` has a line ``XLA Modules`` with one
event per program run, named ``jit_<fn>(<id>)``: ``<fn>`` is the name of
the function the program was traced from (``compact_decode``,
``compact_prefill``, ``copy_page``, ``train_lora_r16``, ...). ``reduce``
gives the device seconds and the runs of each program inside a window,
names without ``jit_`` and ``(<id>)``, so that decode, prefill and each
fine-tuning bank are told apart on the device, which the op names of
``bench.trace`` cannot do.

Events are ``[plane, line, name, start_ns, dur_ns]`` as ``bench.trace.load``
flattens a trace, a module event's name shortened by ``program_name``.
``bench.trace.load`` keeps only the ``XLA Ops`` line today, and its
reduction is what the per-layer readers see; a reader of program time
needs it to keep the ``XLA Modules`` line too.
"""
from __future__ import annotations

import re

from bench.trace import DEVICE_PREFIX

MODULES_LINE = "XLA Modules"
_PROGRAM = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def program_name(module: str) -> str:
    """``jit_compact_decode(1234)`` -> ``compact_decode``."""
    return _PROGRAM.match(module).group(1)


def reduce(events, window_ns=None) -> dict:
    """``module_s`` (device seconds, clipped to the window) and
    ``module_runs`` (runs that overlap it) per program, each summed over
    chips and divided by their number. The window defaults to the first
    run's start to the last run's end."""
    runs = [(p, n, s, s + d) for p, l, n, s, d in events
            if p.startswith(DEVICE_PREFIX) and l == MODULES_LINE]
    if not runs:
        return {}
    lo, hi = window_ns or (min(r[2] for r in runs), max(r[3] for r in runs))
    chips = len({p for p, *_ in runs})
    secs, count = {}, {}
    for _, name, s, e in runs:
        if e <= lo or s >= hi:
            continue
        secs[name] = secs.get(name, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
        count[name] = count.get(name, 0) + 1
    return {"module_s": {k: v / chips for k, v in secs.items()},
            "module_runs": {k: v / chips for k, v in count.items()}}

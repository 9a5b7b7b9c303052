"""Reduction of a profiler trace to device busy time, idle gaps and kernel
time.

What a v5e trace holds (looked at by hand): the plane ``/device:TPU:0``
has a line ``XLA Modules`` (one event per program run, named
``jit_<fn>(<id>)``) and a line ``XLA Ops`` whose events are named by their
HLO text, ``%<op> = <shape> <opcode>(...)``. Control-flow ops (the layer
scan's ``%while.N``) span the ops they run, so op events nest. A Pallas
kernel is a ``tpu_custom_call`` op named after the kernel:
``%paged_decode_attn.13 = ...``, ``%sgmv.68 = ...``. Host spans are the
``repro.obs/<name>`` annotations on the ``/host:CPU`` plane, on the same
clock as the device planes.

A trace is first flattened to events ``[plane, line, name, start_ns,
dur_ns]`` (``load``), device op names shortened to ``<op>``; everything
below works on that list, so the tests check it on a small recorded
fixture.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "repro.obs/"
_OP = re.compile(r"^%?([^ =]+)")


def op_name(hlo: str) -> str:
    """``%sgmv.68 = bf16[...] custom-call(...)`` -> ``sgmv.68``."""
    m = _OP.match(hlo)
    return m.group(1) if m else hlo


def op_base(name: str) -> str:
    """``sgmv.68`` -> ``sgmv``; ``fusion.241`` -> ``fusion``."""
    return re.sub(r"(\.\d+)+$", "", name)


def load(trace_dir: str) -> list:
    """Device ops and host spans of the newest ``.xplane.pb`` under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    pd = ProfileData.from_file(paths[-1])
    out = []
    for pl in pd.planes:
        on_device = pl.name.startswith(DEVICE_PREFIX)
        if not on_device and not pl.name.startswith("/host"):
            continue
        for ln in pl.lines:
            if on_device and ln.name != OPS_LINE:
                continue
            for e in ln.events:
                if on_device:
                    name = op_name(e.name)
                elif e.name.startswith(SPAN_PREFIX):
                    name = e.name
                else:
                    continue
                out.append([pl.name, ln.name, name, float(e.start_ns),
                            float(e.duration_ns)])
    return out


def device_ops(events) -> dict:
    """{plane: [(start_ns, end_ns, name), ...]} of device operations."""
    out = {}
    for plane, line, name, start, dur in events:
        if plane.startswith(DEVICE_PREFIX) and line == OPS_LINE:
            out.setdefault(plane, []).append((start, start + dur, name))
    for v in out.values():
        v.sort(key=lambda x: (x[0], -x[1]))
    return out


def union(intervals) -> list:
    """Merged [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e, *_ in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def self_times(ops) -> dict:
    """Device time per op name, less the time of the ops nested in it (so
    a loop op counts only what its body ops leave uncovered)."""
    out, stack = {}, []          # stack of [end, name, child time]

    def close(item):
        end, name, start, child = item
        out[name] = out.get(name, 0.0) + max(0.0, end - start - child)
        if stack:
            stack[-1][3] += end - start

    for s, e, name in ops:
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([e, name, s, 0.0])
    while stack:
        close(stack.pop())
    return out


def spans(events) -> list:
    """Host spans [(start, end, name without prefix)]."""
    return sorted((s, s + d, n[len(SPAN_PREFIX):])
                  for p, l, n, s, d in events
                  if not p.startswith(DEVICE_PREFIX)
                  and n.startswith(SPAN_PREFIX))


def reduce(events, window_ns=None) -> dict:
    """Busy time per chip (union of op intervals) and its mean over chips,
    the window (given, else first op start to last op end), idle gaps
    between busy intervals labelled by the host span that covers most of
    each gap ("no span" where none does), and device self time per op."""
    ops = device_ops(events)
    if not ops:
        return {}
    if window_ns is None:
        lo = min(v[0][0] for v in ops.values())
        hi = max(max(e for _, e, _ in v) for v in ops.values())
        window_ns = (lo, hi)
    lo, hi = window_ns
    host = spans(events)
    busy, gaps, by_name = [], [], {}
    for v in ops.values():
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in v
                  if e > lo and s < hi]
        merged = union(inside)
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for m in merged for x in m] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b, _label(host, a, b)))
        for name, t in self_times(inside).items():
            by_name[name] = by_name.get(name, 0.0) + t
    n = len(ops)
    return {"chips": n, "window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy) / n * 1e-9,
            "gaps": sorted(gaps, reverse=True),
            "op_s": {k: v * 1e-9 for k, v in by_name.items()}}


def _label(host, a, b) -> str:
    best, best_ov = "no span", 0.0
    for s, e, name in host:
        if s >= b:
            break
        ov = min(e, b) - max(s, a)
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def kernel_seconds(red: dict, kernel: str) -> float:
    """Device seconds of the ops of one kernel (``<kernel>.<n>``), summed
    over chips and divided by their number."""
    if not red:
        return 0.0
    t = sum(v for k, v in red["op_s"].items() if op_base(k) == kernel)
    return t / red["chips"]


def breakdown(red: dict, n: int = 10) -> dict:
    """The device ops that took most self time and the longest idle gaps
    with what the host was doing in them, seconds unrounded."""
    ops = sorted(red.get("op_s", {}).items(), key=lambda kv: -kv[1])[:n]
    gaps = red.get("gaps", [])[:n]
    return {"device_ops": [[k, v / red["chips"]] for k, v in ops],
            "idle_gaps": [[label, d * 1e-9] for d, _, _, label in gaps]}

"""Device seconds and runs per program from a trace's ``XLA Modules``
line: program names, the reduction on a hand-made event list, and on a
small trace recorded on a v5e with both compact serving programs."""
import json
import os

import pytest

import benchtiny  # noqa: F401  puts the repository on sys.path
from bench import programs, trace

D, D1, MOD, OPS, H = ("/device:TPU:0", "/device:TPU:1", "XLA Modules",
                      "XLA Ops", "/host:CPU")
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_v5e_programs.json")


def test_program_names():
    assert programs.program_name("jit_compact_decode(1234)") == \
        "compact_decode"
    assert programs.program_name("jit_train_lora_r16(7)") == "train_lora_r16"
    assert programs.program_name("jit_copy_page") == "copy_page"
    assert programs.program_name("custom") == "custom"


def _events():
    return [
        [D, MOD, "compact_prefill", 0.0, 40.0],
        [D, MOD, "compact_decode", 50.0, 30.0],
        [D, MOD, "copy_page", 85.0, 5.0],
        [D, MOD, "compact_decode", 100.0, 30.0],
        [D, OPS, "fusion.1", 0.0, 40.0],          # other lines: not runs
        [H, "main", "repro.obs/prefill", 0.0, 45.0],
    ]


def test_reduce_by_hand():
    red = programs.reduce(_events())
    assert red["module_s"] == pytest.approx(
        {"compact_prefill": 40e-9, "compact_decode": 60e-9,
         "copy_page": 5e-9})
    assert red["module_runs"] == {"compact_prefill": 1, "compact_decode": 2,
                                  "copy_page": 1}
    # a window clips seconds and counts the runs that overlap it
    red = programs.reduce(_events(), window_ns=(20.0, 110.0))
    assert red["module_s"] == pytest.approx(
        {"compact_prefill": 20e-9, "compact_decode": 40e-9,
         "copy_page": 5e-9})
    assert red["module_runs"]["compact_decode"] == 2
    # two chips: summed and divided by their number
    two = _events() + [[D1, MOD, "compact_decode", 50.0, 10.0]]
    assert programs.reduce(two)["module_s"]["compact_decode"] == \
        pytest.approx(35e-9)
    assert programs.reduce([[H, "main", "repro.obs/admit", 0.0, 5.0]]) == {}


def test_recorded_v5e_serving_programs():
    ev = json.load(open(FIXTURE))
    red = trace.reduce(ev["events"])
    lo = min(s for p, l, n, s, d in ev["events"] if l == OPS)
    hi = max(s + d for p, l, n, s, d in ev["events"] if l == OPS)
    got = programs.reduce(ev["events"], window_ns=(lo, hi))
    assert set(got["module_s"]) == {"compact_decode", "compact_prefill"}
    assert got["module_runs"] == {"compact_decode": 1, "compact_prefill": 1}
    assert got["module_s"] == pytest.approx(ev["module_s"])
    # the programs account for the chip's work: their device seconds
    # cover the busy union of the ops, and the ops reduce as before
    assert red["busy_s"] == pytest.approx(ev["busy_s"])
    assert sum(got["module_s"].values()) >= 0.95 * red["busy_s"]
    # the host spans carry the split decode-tick and prefill names
    names = {n[len(trace.SPAN_PREFIX):] for _, _, n, _, _ in ev["events"]
             if n.startswith(trace.SPAN_PREFIX)}
    assert {"sample", "scatter", "prefill_dispatch", "prefill_sync",
            "jit_dispatch", "device_sync"} <= names

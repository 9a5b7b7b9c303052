"""The trace reduction: busy union, idle share, kernel time by name, self
time of nested ops, and idle gaps labelled by the host span over them; on
a hand-made trace and on a small trace recorded on a v5e."""
import json
import os

import pytest

import benchtiny  # noqa: F401  puts the repository on sys.path
from bench import trace

D, OPS, H = "/device:TPU:0", "XLA Ops", "/host:CPU"
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_v5e.json")


def test_names():
    hlo = ("%paged_decode_attn.13 = bf16[32,8,4,128]{3,2,1,0} custom-call("
           "s32[32,128] %fusion.3), custom_call_target=\"tpu_custom_call\"")
    assert trace.op_name(hlo) == "paged_decode_attn.13"
    assert trace.op_base("paged_decode_attn.13") == "paged_decode_attn"
    assert trace.op_base("sgmv.68") == "sgmv"
    assert trace.op_base("while") == "while"


def _events():
    return [
        [D, OPS, "while.1", 0.0, 100.0],        # a loop over 3 body ops
        [D, OPS, "sgmv.4", 10.0, 20.0],
        [D, OPS, "fusion.2", 30.0, 30.0],
        [D, OPS, "paged_decode_attn.1", 70.0, 20.0],
        [D, OPS, "fusion.9", 150.0, 50.0],       # gap 100..150
        [D, OPS, "sgmv.7", 260.0, 40.0],         # gap 200..260
        [H, "main", "repro.obs/device_sync", 95.0, 60.0],
        [H, "main", "repro.obs/scatter", 205.0, 20.0],
        [H, "main", "repro.obs/admit", 226.0, 30.0],
        [H, "main", "not a span", 100.0, 200.0],
    ]


def test_reduce_by_hand():
    red = trace.reduce(_events())
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(300e-9)
    assert red["busy_s"] == pytest.approx(190e-9)
    gaps = [(d, label) for d, _, _, label in red["gaps"]]
    assert gaps == [(60.0, "admit"), (50.0, "device_sync")]
    assert red["op_s"]["while.1"] == pytest.approx(30e-9)   # 100 - 70
    assert trace.kernel_seconds(red, "sgmv") == pytest.approx(60e-9)
    assert trace.kernel_seconds(red, "paged_decode_attn") == \
        pytest.approx(20e-9)
    bd = trace.breakdown(red)
    assert bd["device_ops"][0] == ["fusion.9", pytest.approx(50e-9)]
    assert bd["idle_gaps"][0] == ["admit", pytest.approx(60e-9)]
    assert sum(red["op_s"].values()) == pytest.approx(red["busy_s"])


def test_window_clips_and_no_device():
    red = trace.reduce(_events(), window_ns=(50.0, 250.0))
    assert red["window_s"] == pytest.approx(200e-9)
    assert red["busy_s"] == pytest.approx(100e-9)     # 50..100 and 150..200
    assert trace.reduce([[H, "main", "repro.obs/admit", 0.0, 5.0]]) == {}
    assert trace.kernel_seconds({}, "sgmv") == 0.0


def test_recorded_v5e_trace():
    ev = json.load(open(FIXTURE))
    red = trace.reduce(ev["events"])
    assert red["chips"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    # nested op events: self times add up to the busy union
    assert sum(red["op_s"].values()) == pytest.approx(red["busy_s"],
                                                      rel=1e-6)
    for k in ("paged_decode_attn", "sgmv"):
        want = sum(d for p, l, n, s, d in ev["events"]
                   if p.startswith(D) and trace.op_base(n) == k) * 1e-9
        assert trace.kernel_seconds(red, k) == pytest.approx(want)
        assert trace.kernel_seconds(red, k) > 0
    labels = {label for _, _, _, label in red["gaps"]}
    assert labels & {"device_sync", "admit", "scatter", "compact_gather",
                     "prefill", "jit_dispatch"}
    assert red["busy_s"] == pytest.approx(ev["busy_s"])

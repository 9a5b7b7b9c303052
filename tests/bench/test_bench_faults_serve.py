"""The comparison that decides ``correct`` in a serving cell fails what it
must: a whole tiny run on the CPU with a token altered where it is produced
comes out incorrect, and the control (the reference one precision lower)
reads past the limit that sound runs stay inside."""
import argparse
import time

import jax

from benchtiny import REPO, run_cell, tiny_root  # noqa: F401  a fixture

from bench import harness
from bench.traffic import open_loop as ol


def test_altered_token_is_incorrect(tiny_root, capsys, monkeypatch):
    from repro.serving.engine import ServingEngine
    sample = ServingEngine._sample

    def altered(self, logits, req):
        return (sample(self, logits, req) + 1) % logits.shape[-1]

    monkeypatch.setattr(ServingEngine, "_sample", altered)
    res = run_cell(tiny_root, "tiny.chat", seed=5, capsys=capsys)
    assert res["correct"] is False
    c = res["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]


def _run(root, workload, seed, seconds=1.0):
    reg = harness.Registry([root, REPO])
    ns = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    return harness.Run(ns, reg.cell(workload), reg, time.perf_counter(),
                       jax.devices()[:1], REPO)


def test_serving_control_reads_past_the_limit(tiny_root):
    run = _run(tiny_root, "tiny.chat", 8)
    svc, obs, compiles, _ = ol.setup(run)
    w = ol.window(run, svc, obs, compiles, run.traffic, 8, 1.0,
                  time.perf_counter())
    got = ol.readings(run, w["recs"], w["kept"], lowp="bf16")
    lim = run.traffic["check"]
    assert got["gap"] <= lim["max_gap"] and got["error"] <= lim["max_error"]
    assert got["control.gap"] > lim["max_gap"] or \
        got["control.error"] > lim["max_error"]



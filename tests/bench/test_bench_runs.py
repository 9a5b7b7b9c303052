"""Whole runs of tiny cells on the CPU through the real entry point, the
look for a chip skipped: a serving and a fine-tuning cell come out correct
with the metrics their entries name; a cell, mix and per-layer metric added
as new files only are found and reported; and without a TPU the entry point
refuses to run."""
import json
import os
import subprocess
import sys

from benchtiny import (CHAT, REPO, run_cell, tiny_root,  # noqa: F401
                       write_root)

NEW_METRIC = '''"""Ticks in the window (a reader added as a file of its own)."""


def read(view):
    a, b = view.snaps["t0"], view.snaps["t1"]
    return float(b[1] - a[1])
'''


def test_serving_cell_with_new_files(tmp_path, capsys):
    root = write_root(tmp_path, extra_metrics=[
        {"name": "window_ticks", "unit": "ticks", "workloads": ["tiny.new"]}])
    # a new configuration, mix and metric: files only, found by name
    cfg = json.load(open(os.path.join(root, "bench", "configs",
                                      "tiny.json")))
    cfg["name"] = "tiny2"
    json.dump(cfg, open(os.path.join(root, "bench", "configs",
                                     "tiny2.json"), "w"))
    mix = dict(CHAT, arrivals={"process": "poisson", "cv": 1.0,
                               "rate_rps": 8.0})
    json.dump(mix, open(os.path.join(root, "bench", "traffic",
                                     "tiny-new.json"), "w"))
    os.makedirs(os.path.join(root, "bench", "metrics"), exist_ok=True)
    with open(os.path.join(root, "bench", "metrics", "window_ticks.py"),
              "w") as f:
        f.write(NEW_METRIC)
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["workloads"].append({"name": "tiny.new", "config": "tiny2",
                               "traffic": "tiny-new", "chips": 1,
                               "why": "added"})
    bench["end_to_end"][0]["workloads"].append("tiny.new")
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    res = run_cell(root, "tiny.new", seed=2**31 + 7, trace=1, capsys=capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    m = res["metrics"]
    # the per-layer metrics whose entries list this cell, and no others
    assert set(m) == {"window_ticks"} and m["window_ticks"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["max_logit_gap"]["value"] <= \
        res["checks"]["max_logit_gap"]["limit"]

    res = run_cell(root, "tiny.new", seed=11, trace=0, capsys=capsys)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"ttft_p90_ms", "setup_s"}
    assert res["device"]["count"] == 1


def test_finetune_cell(tiny_root, capsys):
    res = run_cell(tiny_root, "tiny.ft", seed=4, trace=0, capsys=capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "change_gap"}


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-4b.decode-saturated", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr

"""A tiny benchmark root for the CPU: the real harness, drivers and
readers, with a two-layer model and mixes a test run can hold."""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {
    "name": "tiny-gqa", "arch": "dense", "n_layers": 2, "d_model": 64,
    "n_heads": 4, "n_kv_heads": 2, "d_ff": 128, "vocab": 256,
    "head_dim": 16, "head_pad": 0, "qk_norm": True, "tie_embeddings": True,
    "rope_theta": 10000.0, "n_experts": 0, "top_k": 0, "n_shared_experts": 0,
    "d_expert": 0, "moe_every": 1, "moe_offset": 0, "first_dense_layers": 0,
    "dense_residual": False, "attn_every": 0, "d_state": 16, "d_conv": 4,
    "mamba_expand": 2, "n_enc_layers": 0, "n_frontend_tokens": 0,
    "sliding_window": 0, "dtype": "float32", "param_dtype": "float32",
    "source": "test"}

BANKS = [
    {"name": "lora", "method": "lora", "rank": 4, "alpha": 8.0,
     "targets": ["q", "k", "v", "o"], "n_prefix": 0, "clients": 2},
    {"name": "ia3", "method": "ia3", "rank": 0, "alpha": 0.0,
     "targets": ["k", "v", "down"], "n_prefix": 0, "clients": 2},
    {"name": "prefix", "method": "prefix", "rank": 0, "alpha": 0.0,
     "targets": ["q", "v"], "n_prefix": 4, "clients": 2}]

CHAT = {
    "kind": "open_loop", "banks": BANKS, "popularity": {"zipf_s": 1.0},
    "arrivals": {"process": "gamma", "cv": 2.0, "rate_rps": 6.0},
    "prompt": {"median": 24, "sigma": 0.5, "min": 8, "max": 60},
    "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 12},
    "sizes_seed": 0,
    "serve": {"max_seq": 96, "page_block": 8, "pool_pages": 24,
              "max_batch_per_client": 2},
    "load": "below_knee", "ramp_s": 0, "drain_s": 30,
    "trace": {"offset_s": 0.2, "seconds": 0.3},
    "check": {"retain_tokens": 60, "tokens": 20, "max_gap": 1e-3,
              "max_error": 1e-4}}

FINETUNE = {
    "kind": "finetune_jobs",
    "jobs": [
        {"name": "lora-a", "method": "lora", "rank": 4, "alpha": 8.0,
         "targets": ["q", "v"]},
        {"name": "lora-b", "method": "lora", "rank": 4, "alpha": 8.0,
         "targets": ["q", "v"]},
        {"name": "ia3", "method": "ia3", "targets": ["k", "v", "down"]},
        {"name": "prefix", "method": "prefix", "n_prefix": 4,
         "targets": ["q", "v"]}],
    "seq": 16, "rows": 2, "remat": True,
    "opt": {"lr": 1e-2, "warmup": 0, "total": 100, "wd": 0.0,
            "max_grad_norm": 1.0},
    # sound runs read about 1e-7 (loss), 3e-7 (gradient) and 1e-5 (change);
    # the bfloat16 control about 5e-5, 4e-3 and 5e-3
    "check": {"steps": 2, "loss": 1e-5, "grad": 1e-4, "change": 1e-4},
    "trace": {"offset_s": 0.1, "seconds": 0.2}}


def write_root(root, *, chat=None, finetune=None, extra_metrics=()):
    """A benchmark root: BENCHMARK.json naming a tiny serving cell and a
    tiny fine-tuning cell, their config and mixes, and a peak entry for the
    CPU (a number for the readers' arithmetic, not a chip's)."""
    os.makedirs(os.path.join(root, "bench", "configs"), exist_ok=True)
    os.makedirs(os.path.join(root, "bench", "traffic"), exist_ok=True)
    cfg = {"name": "tiny", "source": "test", "model": TINY_MODEL,
           "reduced": [], "published": {}}
    files = {("configs", "tiny.json"): cfg,
             ("traffic", "tiny-chat.json"): chat or CHAT,
             ("traffic", "tiny-ft.json"): finetune or FINETUNE,
             ("peaks.json",): {"source": "test", "chips": {"cpu": {
                 "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                 "hbm_bytes": 1e9}}}}
    for parts, d in files.items():
        with open(os.path.join(root, "bench", *parts), "w") as f:
            json.dump(d, f)
    bench = {
        "workloads": [
            {"name": "tiny.chat", "config": "tiny", "traffic": "tiny-chat",
             "chips": 1, "why": "test"},
            {"name": "tiny.ft", "config": "tiny", "traffic": "tiny-ft",
             "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "ttft_p90_ms", "unit": "ms", "workloads": ["tiny.chat"]},
            {"name": "tpot_p90_ms", "unit": "ms", "workloads": ["tiny.chat"]},
            {"name": "train_tokens_per_s", "unit": "tokens/s",
             "workloads": ["tiny.ft"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "prefill_share", "unit": "%", "workloads": ["tiny.chat"]},
            {"name": "decode_rows_per_step", "unit": "rows",
             "workloads": ["tiny.chat"]},
            {"name": "mfu.train", "unit": "%", "workloads": ["tiny.ft"]},
        ] + list(extra_metrics)}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return str(root)


def run_cell(root, workload, seed=3, seconds=1.0, trace=0, capsys=None):
    """One run of a tiny cell on the CPU through the real entry point,
    without the look for a chip; returns the result line as a dict."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_run_main", os.path.join(REPO, "bench", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    import time
    rc = mod.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  roots=[root, REPO], require_tpu=False,
                  t_start=time.perf_counter())
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.fixture
def tiny_root(tmp_path):
    return write_root(tmp_path)

"""A model family plugs into the benchmark by files alone: a family module
(``fixtures/arch/moe.py``, the program's drop-free mixture of experts) and
a configuration naming it, written under a second benchmark root with a
mix and a cell, run a tiny serving cell through the open-loop driver with
the family's weights and reference, and come out correct; the per-layer
readers count the family's FLOPs, and its published keys and cuts are the
family's own. No file of the benchmark is edited."""
import json
import os
import shutil
from types import SimpleNamespace

import pytest

from benchtiny import BANKS, CHAT, REPO, TINY_MODEL, run_cell, write_root
from test_bench_configs import check_published

from bench.harness import Registry

MODULE = os.path.join(os.path.dirname(__file__), "fixtures", "arch",
                      "moe.py")
MOE = dict(TINY_MODEL, name="tiny-moe", arch="moe", n_experts=4, top_k=2,
           d_expert=32)
PUBLISHED = {"hidden_size": 64, "num_hidden_layers": 4,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 16, "intermediate_size": 128,
             "moe_intermediate_size": 32, "num_experts": 4,
             "num_experts_per_tok": 2, "vocab_size": 256,
             "tie_word_embeddings": True, "rope_theta": 10000.0,
             "torch_dtype": "float32"}
# LoRA on the attention projections, and a second bank so the engine
# serves mixed banks, as the open-loop driver drives it
MIX = dict(CHAT, banks=[BANKS[0], dict(BANKS[1], targets=["k", "v"])])


def _root(tmp_path):
    root = write_root(tmp_path)
    os.makedirs(os.path.join(root, "bench", "arch"))
    shutil.copy(MODULE, os.path.join(root, "bench", "arch", "moe.py"))
    cfg = {"name": "tiny-moe", "source": "test", "model": MOE,
           "published": PUBLISHED, "reduced": ["num_hidden_layers"]}
    for parts, d in ((("configs", "tiny-moe.json"), cfg),
                     (("traffic", "tiny-moe-chat.json"), MIX)):
        with open(os.path.join(root, "bench", *parts), "w") as f:
            json.dump(d, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny-moe.chat", "config": "tiny-moe",
                               "traffic": "tiny-moe-chat", "chips": 1,
                               "why": "a second family"})
    bench["end_to_end"][0]["workloads"].append("tiny-moe.chat")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_a_new_family_serves_a_tiny_cell(tmp_path, capsys):
    root = _root(tmp_path)
    reg = Registry([root, REPO])
    cell = reg.cell("tiny-moe.chat")
    fam = reg.arch(cell["config"]["model"]["arch"])
    assert fam.__file__.startswith(root)
    paths = {p[0] for p in fam.base_leaves(MOE)}
    assert ("layers", "moe", "experts", "gate") in paths
    assert not any("mlp" in p for p in paths)
    check_published(cell["config"], fam)

    res = run_cell(root, "tiny-moe.chat", seed=2**31 + 3, capsys=capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    assert set(res["metrics"]) == {"ttft_p90_ms", "setup_s"}


def _view(family):
    rec = SimpleNamespace(client=0, S=5, n=3, admit_tick=1,
                          req=SimpleNamespace(status="ok"))
    return SimpleNamespace(
        cfg=MOE, family=family, mix=MIX, recs=[rec],
        peaks={"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e4},
        snaps={"tr0": (0.0, 1, {}, {}), "tr1": (2.0, 3, {}, {})},
        trace={"chips": 1, "op_s": {}, "busy_s": 1.0, "host_window_s": 2.0})


def test_readers_count_the_family(tmp_path):
    reg = Registry([_root(tmp_path), REPO])
    moe, dense = reg.arch("moe"), reg.arch("dense")
    # the prompt of 5 at tick 1, then tokens 1 and 2 (6 and 7 keys) at
    # ticks 1 and 2; a token touches attention, router, 2 of 4 experts of
    # width 32, and the tied head; LoRA r4 on q, k, v, o
    d, hd, H, K, V, L, fe = 64, 16, 4, 2, 256, 2, 32
    P = L * (d * (H + 2 * K) * hd + H * hd * d + d * 4 + 2 * 3 * d * fe) \
        + d * V
    lora = 2 * 4 * L * ((d + H * hd) + 2 * (d + K * hd) + (H * hd + d))
    tok = [2 * P + 4 * keys * H * hd * L + lora for keys in (0, 6, 7)]
    total = 5 * (tok[0] - 2 * d * V) + 2 * d * V + 4 * 15 * H * hd * L \
        + tok[1] + tok[2]
    got = reg.reader("mfu.serve").read(_view(moe))
    assert got == pytest.approx(100 * total / (2.0 * 1e6))
    assert got != pytest.approx(reg.reader("mfu.serve").read(_view(dense)))
    job = {"method": "lora", "rank": 4, "targets": ["q", "k", "v", "o"]}
    assert moe.train_flops(_view(moe), job, 15) == pytest.approx(
        4 * P + 3 * 4 * 8 * H * hd * L + 3 * lora)


def test_published_cuts_are_the_family_s(tmp_path):
    reg = Registry([_root(tmp_path), REPO])
    held = {"name": "tiny-moe", "model": MOE, "reduced": ["num_experts"],
            "published": dict(PUBLISHED, num_hidden_layers=2,
                              num_experts=16)}
    check_published(held, reg.arch("moe"))
    with pytest.raises(AssertionError):
        check_published(held, reg.arch("dense"))
    with pytest.raises(AssertionError):
        check_published(dict(held, reduced=[]), reg.arch("moe"))

"""The per-layer readers' operation and byte counts against hand counts on
a small hand-made window."""
from types import SimpleNamespace

import pytest

from benchtiny import REPO

from bench.harness import Registry

CFG = {"arch": "dense", "d_model": 8, "head_dim": 2, "n_heads": 4,
       "n_kv_heads": 2, "d_ff": 16, "vocab": 32, "n_layers": 3}
PEAK = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e4}
MIX = {"banks": [{"name": "lora", "method": "lora", "rank": 2,
                  "targets": ["q", "o"], "clients": 1},
                 {"name": "pfx", "method": "prefix", "n_prefix": 3,
                  "targets": [], "clients": 1}]}


def _rec(client, S, n, admit):
    req = SimpleNamespace(status="ok")
    return SimpleNamespace(client=client, S=S, n=n, admit_tick=admit,
                           req=req)


def _view(kernel_s):
    # ticks 0..9; the traced window holds ticks [2, 4)
    recs = [_rec(0, 5, 4, 1),     # decode tokens 1,2,3 at ticks 1,2,3
            _rec(1, 7, 2, 3)]     # prefill at tick 3, token 1 at tick 3
    op_s = {"paged_decode_attn.3": kernel_s, "sgmv.1": kernel_s / 2,
            "sgmv.2": kernel_s / 2, "fusion.1": 9.0}
    return SimpleNamespace(
        cfg=CFG, mix=MIX, recs=recs, peaks=PEAK,
        snaps={"tr0": (10.0, 2, {}, {}), "tr1": (12.0, 4, {}, {})},
        trace={"chips": 1, "op_s": op_s, "busy_s": 1.5,
               "host_window_s": 2.0})


def reader(name):
    return Registry([REPO]).reader(name)


def test_paged_decode_attention_roofline():
    # decode rows in ticks 2 and 3: rec0 token 2 (keys 7) at tick 2, rec0
    # token 3 (keys 8) and rec1 token 1 (keys 8) at tick 3
    H, K, hd, L = 4, 2, 2, 3

    def cost(keys):
        return 2 * (2 * keys * K * hd + 2 * H * hd), 4 * keys * H * hd

    b2, f2 = cost(7)
    b3 = cost(8)[0] * 2
    f3 = cost(8)[1] * 2
    least = (max(b2 / 1e4, f2 / 1e6) + max(b3 / 1e4, f3 / 1e6)) * L
    got = reader("paged_decode_attn_roofline").read(_view(0.5))
    assert got == pytest.approx(100 * least / 0.5)


def test_sgmv_roofline():
    # LoRA rows in the window: client 0 decodes one token at ticks 2 and 3;
    # targets q (8 -> 8) and o (8 -> 8), rank 2, one adapter per call
    per_target = 2 * (1 * 16 + 1 * 2 * 16)          # bytes
    fl = 2 * 1 * 2 * 16
    least = 2 * 2 * max(per_target / 1e4, fl / 1e6) * 3
    got = reader("sgmv_roofline").read(_view(0.5))
    assert got == pytest.approx(100 * least / 0.5)


def test_mfu_serve():
    d, hd, H, K, ff, V, L = 8, 2, 4, 2, 16, 32, 3
    P = L * (d * (H + 2 * K) * hd + H * hd * d + 3 * d * ff) + d * V
    lora = 2 * 2 * ((d + H * hd) + (H * hd + d)) * L
    pfx = 4 * 3 * H * hd * L

    def tok(keys, extra):
        return 2 * P + 4 * keys * H * hd * L + extra

    total = tok(7, lora) + tok(8, lora) + tok(8, pfx)
    # rec1's 7-token prompt: head once, attention over 1..7 keys
    total += 7 * (tok(0, pfx) - 2 * d * V) + 2 * d * V + 4 * 28 * H * hd * L
    got = reader("mfu.serve").read(_view(0.5))
    assert got == pytest.approx(100 * total / (2.0 * 1e6))


def test_idle_share_and_silence():
    assert reader("idle_share.serve").read(_view(0.5)) == pytest.approx(25.0)
    empty = SimpleNamespace(trace={}, snaps={})
    for name in ("idle_share.serve", "paged_decode_attn_roofline",
                 "sgmv_roofline", "mfu.serve"):
        assert reader(name).read(empty) is None
    no_kernel = _view(0.5)
    no_kernel.trace["op_s"] = {"fusion.1": 1.0}
    assert reader("sgmv_roofline").read(no_kernel) is None


def test_mfu_train():
    mix = {"seq": 4, "rows": 2, "jobs": [
        {"method": "lora", "rank": 2, "targets": ["q"]},
        {"method": "ia3", "targets": ["k"]}]}
    view = SimpleNamespace(cfg=CFG, mix=mix, peaks=PEAK, trace={"x": 1},
                           snaps={"tr0": (0.0, 3), "tr1": (4.0, 5)})
    d, hd, H, K, ff, V, L = 8, 2, 4, 2, 16, 32, 3
    P = L * (d * (H + 2 * K) * hd + H * hd * d + 3 * d * ff) + d * V
    base = 4 * P + 3 * 4 * 2.5 * H * hd * L
    per_step = (base + 3 * 2 * 2 * (d + H * hd) * L + base) * 2 * 4
    got = reader("mfu.train").read(view)
    assert got == pytest.approx(100 * 2 * per_step / (4.0 * 1e6))

"""A mixture-of-experts decoder family (``"arch": "moe"``), as the
program's drop-free expert layer runs it: the dense family's attention
half, then a router over the ``n_experts`` experts held (softmax, the top
``top_k`` per token, their weights renormalised to sum to one) and a
SwiGLU expert of width ``d_expert`` each, every layer an expert layer, no
shared expert. Adapters target the attention projections (q, k, v, o).

It gives what ``bench/arch/dense.py`` lists for a family module. The
plug-in test copies it to ``bench/arch/moe.py`` under a second benchmark
root, so a tiny serving cell of this family runs from new files alone.
Held to the tiny float32 sizes of that test: the reference computes every
expert for every token and weights each by its routing share, and the
router is drawn in the served dtype, where the program's own initialiser
keeps it in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.arch import dense
from bench.reference import rms

PUBLISHED = dict(dense.PUBLISHED, moe_intermediate_size="d_expert",
                 num_experts="n_experts", num_experts_per_tok="top_k")
# the depth, as one stage of a pipeline; the experts, as this chip's share
# of an expert-parallel layer
CUTS = {"num_hidden_layers", "num_experts"}
implied = dense.implied

ATTENTION = ("q", "k", "v", "o")


def _width(cfg: dict) -> int:
    return cfg["d_expert"] or cfg["d_ff"]


def base_leaves(cfg: dict):
    """The dense family's leaves less its feed-forward, then the router and
    the experts stacked [E, ...] under ``layers/moe``."""
    d, E, fe = cfg["d_model"], cfg["n_experts"], _width(cfg)
    out = [x for x in dense.base_leaves(cfg) if x[0][1:2] != ("mlp",)]
    for path, shape, fan in ((("router",), (d, E), d),
                             (("experts", "gate"), (E, d, fe), d),
                             (("experts", "up"), (E, d, fe), d),
                             (("experts", "down"), (E, fe, d), fe)):
        out.append((("layers", "moe") + path, "linear", "layer", shape, fan))
    return out


def target_dims(cfg: dict) -> dict:
    dims = dense.target_dims(cfg)
    return {t: dims[t] for t in ATTENTION}


def layer_weights(layers, li=None) -> dict:
    """The dense family's short names, plus ``router`` and
    ``expert_<gate|up|down>``."""
    moe = layers["moe"]
    w = dense.layer_weights({g: v for g, v in layers.items() if g != "moe"},
                            li)
    flat = {"router": moe["router"]}
    flat.update({"expert_" + n: x for n, x in moe["experts"].items()})
    w.update(dense.layer_weights({"moe": flat}, li))
    return w


def layer(cfg: dict, w: dict, ad: dict, method: dict, x, mm):
    x = dense.attention(cfg, w, ad, method, x, mm)
    h = rms(x, w["ln2"])
    probs = jax.nn.softmax(mm("td,de->te", h, w["router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["top_k"])
    top = top / (top.sum(-1, keepdims=True) + 1e-9)
    share = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx].set(top)          # [T, E]
    g = mm("td,edf->etf", h, w["expert_gate"])
    u = mm("td,edf->etf", h, w["expert_up"])
    y = mm("etf,efd->etd", jax.nn.silu(g) * u, w["expert_down"])
    return x + mm("te,etd->td", share, y)


# ---------------------------------------------------------------------------
# Required FLOPs: the routed experts of a token, not every expert held
# ---------------------------------------------------------------------------

def _frozen(cfg: dict) -> int:
    """Frozen parameters a token's projections touch: attention, router,
    ``top_k`` experts, and the head."""
    d, hd, L = cfg["d_model"], cfg["head_dim"], cfg["n_layers"]
    H, K, V = cfg["n_heads"], cfg["n_kv_heads"], cfg["vocab"]
    return L * (d * (H + 2 * K) * hd + H * hd * d + d * cfg["n_experts"]
                + cfg["top_k"] * 3 * d * _width(cfg)) + d * V


def _adapter(cfg: dict, method: dict) -> float:
    dims, L = target_dims(cfg), cfg["n_layers"]
    if method["method"] == "lora":
        return 2.0 * method["rank"] * L * sum(sum(dims[t])
                                              for t in method["targets"])
    if method["method"] == "prefix":
        return 4.0 * method["n_prefix"] * cfg["n_heads"] * cfg["head_dim"] * L
    return 0.0


def _attend(cfg: dict, keys) -> float:
    return 4.0 * keys * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"]


def serve_flops(view, bank: dict, keys) -> float:
    cfg = view.cfg
    return 2.0 * _frozen(cfg) + _attend(cfg, keys) + _adapter(cfg, bank)


def prefill_flops(view, bank: dict, S: int) -> float:
    cfg = view.cfg
    head = 2.0 * cfg["d_model"] * cfg["vocab"]
    return S * (serve_flops(view, bank, 0) - head) + head \
        + _attend(cfg, S * (S + 1) / 2)


def train_flops(view, job: dict, seq: int) -> float:
    cfg = view.cfg
    return 4.0 * _frozen(cfg) + 3 * _attend(cfg, (seq + 1) / 2.0) \
        + 3 * _adapter(cfg, job)

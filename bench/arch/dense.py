"""The dense decoder family (``"arch": "dense"``): a GQA decoder with
RoPE, optional qk-norm and a SwiGLU feed-forward, as the program lays it
out, with LoRA, IA3 and prefix adapters.

Everything the benchmark knows of one model family sits in one module,
``bench/arch/<arch>.py``, found by the configuration's own
``model["arch"]`` (``harness.Registry.arch``). Each family module gives:

- ``PUBLISHED``, ``implied`` and ``CUTS``: the published config's keys
  and the keys of the configuration as run that they set, the sizes the
  published config leaves to the family's convention, and the published
  keys that ``reduced`` may cut (each only downwards);
- ``base_leaves``: every base leaf, with how it is drawn;
- ``target_dims``: the adapter targets' widths;
- ``layer_weights`` and ``layer``: one layer of the plain reference;
- ``serve_flops``, ``prefill_flops`` and ``train_flops``: the FLOPs that a
  served token, a prefilled prompt and a trained token require, from a
  metric reader's view (so a family whose work depends on routing can
  count it from a program counter).

The reference's layer is split in two: ``attention`` (with its residual)
and ``ffn`` (with its residual), so a family that changes only the
feed-forward imports the attention half.

Adapter semantics (per layer, per client):

- LoRA on a target t: ``y_t = x W_t + (alpha / r) * (x A_t) B_t``;
- IA3: the outputs of k and v are scaled elementwise (before qk-norm), and
  the input of ``down`` (the SwiGLU product) is scaled elementwise;
- prefix: a second softmax branch per layer over ``n_prefix`` virtual keys
  and values, ``0.1 * softmax((h W_q) P_k^T / sqrt(hd)) P_v W_o``, with
  the un-normalised, un-rotated query, added to the attention output.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import rms, rope

# published key -> key of the configuration as run; widths are never cut
PUBLISHED = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads",
             "intermediate_size": "d_ff", "vocab_size": "vocab",
             "head_dim": "head_dim", "tie_word_embeddings": "tie_embeddings",
             "rope_theta": "rope_theta"}
# what ``reduced`` may name: the depth, as one stage of a pipeline
CUTS = {"num_hidden_layers"}


def implied(published: dict) -> dict:
    """Sizes of the configuration as run that the published config leaves
    to the family's convention."""
    if "head_dim" in published:
        return {}
    return {"head_dim": published["hidden_size"]
            // published["num_attention_heads"]}


# ---------------------------------------------------------------------------
# Leaves
# ---------------------------------------------------------------------------

def base_leaves(cfg: dict):
    """(path, kind, per-layer shape or None, fan_in) of every base leaf of a
    dense GQA decoder as the program lays it out: stacked layers under
    ``layers``, tied or separate head."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    H, K, ff, V = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_ff"], cfg["vocab"]
    out = [(("embed",), "embed", None, (V, d), 1),
           (("final_norm", "scale"), "norm", None, (d,), 1)]
    if not cfg["tie_embeddings"]:
        out.append((("lm_head",), "linear", None, (d, V), d))
    lay = [(("ln1", "scale"), "norm", (d,), 1),
           (("ln2", "scale"), "norm", (d,), 1),
           (("attn", "wq"), "linear", (d, H * hd), d),
           (("attn", "wk"), "linear", (d, K * hd), d),
           (("attn", "wv"), "linear", (d, K * hd), d),
           (("attn", "wo"), "linear", (H * hd, d), H * hd),
           (("mlp", "gate"), "linear", (d, ff), d),
           (("mlp", "up"), "linear", (d, ff), d),
           (("mlp", "down"), "linear", (ff, d), ff)]
    if cfg["qk_norm"]:
        lay += [(("attn", "q_norm"), "norm", (hd,), 1),
                (("attn", "k_norm"), "norm", (hd,), 1)]
    for path, kind, shape, fan in lay:
        out.append((("layers",) + path, kind, "layer", shape, fan))
    return out


def target_dims(cfg: dict) -> dict:
    """Adapter target -> (d_in, d_out) of the projection it adapts."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    H, K, ff = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_ff"]
    return {"q": (d, H * hd), "k": (d, K * hd), "v": (d, K * hd),
            "o": (H * hd, d), "gate": (d, ff), "up": (d, ff), "down": (ff, d)}


def layer_weights(layers, li=None) -> dict:
    """One layer of the stacked base leaves, in float32, by short name
    (``ln1``, ``wq``, ``gate``, ...): layer ``li``, or ``layers`` as it
    stands where it holds one layer already (``li`` None)."""
    def one(x):
        if li is not None:
            x = jax.lax.dynamic_index_in_dim(x, li, 0, keepdims=False)
        return x.astype(jnp.float32)

    return {(group if name == "scale" else name): one(x)
            for group, leaves in layers.items()
            for name, x in leaves.items()}


# ---------------------------------------------------------------------------
# The reference's layer
# ---------------------------------------------------------------------------

def project(h, t, wt, ad: dict, method: dict, mm):
    """``h @ wt`` with the adapter's change to target ``t``: LoRA's low-rank
    update, or IA3's scale of the output (``down``'s scale is on its input,
    see ``ffn``)."""
    m = method["method"] if method else ""
    y = mm("ti,io->to", h, wt)
    if m == "lora" and t in ad:
        scale = method["alpha"] / method["rank"]
        y = y + scale * mm("tr,ro->to", mm("ti,ir->tr", h, ad[t]["A"]),
                           ad[t]["B"])
    if m == "ia3" and t in ad and t != "down":
        y = y * ad[t]["scale"]
    return y


def attention(cfg: dict, w: dict, ad: dict, method: dict, x, mm):
    """The attention half of a layer on one sequence, with its residual:
    causal GQA with RoPE (and qk-norm where the configuration has it), the
    prefix branch of a prefix adapter."""
    T = x.shape[0]
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    m = method["method"] if method else ""
    pos = jnp.arange(T)
    h = rms(x, w["ln1"])
    q = project(h, "q", w["wq"], ad, method, mm).reshape(T, H, hd)
    k = project(h, "k", w["wk"], ad, method, mm).reshape(T, K, hd)
    v = project(h, "v", w["wv"], ad, method, mm).reshape(T, K, hd)
    if cfg["qk_norm"]:
        q, k = rms(q, w["q_norm"]), rms(k, w["k_norm"])
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    G = H // K
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = mm("thd,shd->hts", q, k) / math.sqrt(hd)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    att = mm("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    out = project(att.reshape(T, H * hd), "o", w["wo"], ad, method, mm)
    if m == "prefix":
        qp = mm("ti,io->to", h, w["wq"]).reshape(T, K, G, hd)
        sp = mm("tkgh,pkh->tkgp", qp, ad["prefix_k"]) / math.sqrt(hd)
        op = mm("tkgp,pkh->tkgh", jax.nn.softmax(sp, axis=-1), ad["prefix_v"])
        out = out + 0.1 * mm("ti,io->to", op.reshape(T, H * hd), w["wo"])
    return x + out


def ffn(cfg: dict, w: dict, ad: dict, method: dict, x, mm):
    """The SwiGLU feed-forward half of a layer, with its residual."""
    m = method["method"] if method else ""
    h = rms(x, w["ln2"])
    a = jax.nn.silu(project(h, "gate", w["gate"], ad, method, mm)) * \
        project(h, "up", w["up"], ad, method, mm)
    if m == "ia3" and "down" in ad:
        a = a * ad["down"]["scale"]
    return x + project(a, "down", w["down"], ad, method, mm)


def layer(cfg: dict, w: dict, ad: dict, method: dict, x, mm):
    """One decoder layer on one sequence. x [T, d] float32; ``w`` one
    layer's base leaves (``layer_weights``), ``ad`` one adapter's leaves at
    this layer (or empty), ``method`` its description (or None)."""
    return ffn(cfg, w, ad, method, attention(cfg, w, ad, method, x, mm), mm)


# ---------------------------------------------------------------------------
# Required FLOPs
# ---------------------------------------------------------------------------

def frozen_params(cfg: dict) -> int:
    """Frozen projection and head parameters a token's matmuls touch:
    ``P = L * (d*(H+2K)*hd + H*hd*d + 3*d*ff) + d*V``."""
    d, hd, L = cfg["d_model"], cfg["head_dim"], cfg["n_layers"]
    H, K, ff, V = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_ff"], cfg["vocab"]
    return L * (d * (H + 2 * K) * hd + H * hd * d + 3 * d * ff) + d * V


def serve_flops(view, bank: dict, keys) -> float:
    """FLOPs a served token requires: ``2 * P`` of frozen projections and
    head (``frozen_params``), ``4 * keys * H * hd`` per layer of attention
    over the keys it attends, and what its adapter needs: LoRA
    ``2 * r * (d_in + d_out)`` per target and layer, prefix
    ``4 * n_prefix * H * hd`` per layer."""
    cfg = view.cfg
    hd, L, H = cfg["head_dim"], cfg["n_layers"], cfg["n_heads"]
    P = frozen_params(cfg)
    f = 2.0 * P + 4.0 * keys * H * hd * L
    if bank["method"] == "lora":
        dims = target_dims(cfg)
        io = sum(sum(dims[t]) for t in bank["targets"])
        f += 2.0 * bank["rank"] * io * L
    if bank["method"] == "prefix":
        f += 4.0 * bank["n_prefix"] * H * hd * L
    return f


def prefill_flops(view, bank: dict, S: int) -> float:
    """FLOPs a prompt of ``S`` tokens requires: prompt position i attends
    i + 1 keys (sum S(S+1)/2), and only the last position needs the
    head."""
    cfg = view.cfg
    head = 2.0 * cfg["d_model"] * cfg["vocab"]
    return (S * (serve_flops(view, bank, 0) - head) + head
            + 4.0 * (S * (S + 1) / 2) * cfg["n_heads"]
            * cfg["head_dim"] * cfg["n_layers"])


def train_flops(view, job: dict, seq: int) -> float:
    """FLOPs a trained token requires: the forward pass, ``2 * P`` as in
    ``serve_flops``, and the backward pass to the activations, another
    ``2 * P`` (the frozen weights need no gradient); causal attention over
    ``(seq + 1) / 2`` keys on average, ``4 * keys * H * hd`` per layer
    forward and twice that backward; adapters three times their forward
    work (forward, backward to activations, their own gradient).
    Recomputation (remat) does not count."""
    cfg = view.cfg
    hd, L, H = cfg["head_dim"], cfg["n_layers"], cfg["n_heads"]
    P = frozen_params(cfg)
    keys = (seq + 1) / 2.0
    f = 4.0 * P + 3 * 4.0 * keys * H * hd * L
    if job["method"] == "lora":
        dims = target_dims(cfg)
        io = sum(sum(dims[t]) for t in job["targets"])
        f += 3 * 2.0 * job["rank"] * io * L
    if job["method"] == "prefix":
        f += 3 * 4.0 * job["n_prefix"] * H * hd * L
    return f

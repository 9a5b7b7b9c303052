"""Fine-tuning model step: FLOPs the trained tokens of the traced window
require, over the window times the chip's bf16 peak, in %.

A trained token requires the forward pass, ``2 * P`` FLOPs of frozen
projections and head (``P = L * (d*(H+2K)*hd + H*hd*d + 3*d*ff) + d*V``),
and the backward pass to the activations, another ``2 * P``; the frozen
weights need no gradient. Causal attention over ``(S + 1) / 2`` keys on
average costs ``4 * keys * H * hd`` per layer forward and twice that
backward. Adapters add three times their forward work (forward, backward
to activations, their own gradient): LoRA ``2 * r * (d_in + d_out)`` per
target and layer, prefix ``4 * n_prefix * H * hd`` per layer.
Recomputation (remat) does not count."""


def flops_per_token(cfg, job, seq):
    d, hd, L = cfg["d_model"], cfg["head_dim"], cfg["n_layers"]
    H, K, ff, V = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_ff"], cfg["vocab"]
    P = L * (d * (H + 2 * K) * hd + H * hd * d + 3 * d * ff) + d * V
    keys = (seq + 1) / 2.0
    f = 4.0 * P + 3 * 4.0 * keys * H * hd * L
    if job["method"] == "lora":
        dims = {"q": d + H * hd, "k": d + K * hd, "v": d + K * hd,
                "o": H * hd + d, "gate": d + ff, "up": d + ff, "down": ff + d}
        f += 3 * 2.0 * job["rank"] * sum(dims[t] for t in job["targets"]) * L
    if job["method"] == "prefix":
        f += 3 * 4.0 * job["n_prefix"] * H * hd * L
    return f


def read(view):
    if not getattr(view, "trace", None) or "tr0" not in view.snaps \
            or "tr1" not in view.snaps:
        return None
    (ta, i0), (tb, i1) = view.snaps["tr0"], view.snaps["tr1"]
    steps = i1 - i0
    if steps <= 0:
        return None
    mix, cfg = view.mix, view.cfg
    per_step = sum(flops_per_token(cfg, j, mix["seq"]) * mix["rows"]
                   * mix["seq"] for j in mix["jobs"])
    return 100.0 * steps * per_step / ((tb - ta)
                                       * view.peaks["bf16_flops_per_s"])

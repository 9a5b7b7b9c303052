"""Serving model step: model FLOPs of every token the traced window
computed, prefill and decode, over the window times the chip's bf16 peak,
in %.

A token costs ``2 * P`` FLOPs of frozen projections and head
(``P = L * (d*(H+2K)*hd + H*hd*d + 3*d*ff) + d*V``), ``4 * keys * H * hd``
per layer of attention over the keys it attends (its position plus one in
a prompt, its context in decode), and what its adapter needs: LoRA
``2 * r * (d_in + d_out)`` per target and layer, prefix
``4 * n_prefix * H * hd`` per layer. Padding and recomputed projections do
not count."""
from bench import window


def flops_per_token(cfg, bank, keys):
    d, hd, L = cfg["d_model"], cfg["head_dim"], cfg["n_layers"]
    H, K, ff, V = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_ff"], cfg["vocab"]
    P = L * (d * (H + 2 * K) * hd + H * hd * d + 3 * d * ff) + d * V
    f = 2.0 * P + 4.0 * keys * H * hd * L
    if bank["method"] == "lora":
        dims = {"q": d + H * hd, "k": d + K * hd, "v": d + K * hd,
                "o": H * hd + d, "gate": d + ff, "up": d + ff, "down": ff + d}
        f += 2.0 * bank["rank"] * sum(dims[t] for t in bank["targets"]) * L
    if bank["method"] == "prefix":
        f += 4.0 * bank["n_prefix"] * H * hd * L
    return f


def read(view):
    tr = getattr(view, "trace", None)
    span = window.traced_ticks(view) if tr else None
    if not span:
        return None
    cfg, bank = view.cfg, window.bank_of(view)
    total = 0.0
    for _, c, keys in window.decode_rows(view, *span):
        total += flops_per_token(cfg, bank[c], keys)
    for _, c, S in window.prefill_rows(view, *span):
        # prompt position i attends i + 1 keys (sum S(S+1)/2), and only
        # the last position needs the head
        head = 2.0 * cfg["d_model"] * cfg["vocab"]
        total += (S * (flops_per_token(cfg, bank[c], 0) - head) + head
                  + 4.0 * (S * (S + 1) / 2) * cfg["n_heads"]
                  * cfg["head_dim"] * cfg["n_layers"])
    secs = window.traced_seconds(view)
    return 100.0 * total / (secs * view.peaks["bf16_flops_per_s"])

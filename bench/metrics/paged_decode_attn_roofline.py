"""Paged decode attention kernel: its least possible time over its device
time in the traced window, in %.

Per decode row and layer the kernel needs the keys and values of the
row's real context, ``keys * n_kv_heads * head_dim * 2`` elements of bf16,
plus the row's query and output (``2 * n_heads * head_dim`` bf16), and
``4 * keys * n_heads * head_dim`` FLOPs (scores and weighted values). One
kernel call serves one tick's rows at one layer; its least time is the
larger of its bytes over the HBM bandwidth and its FLOPs over the peak.
Padding rows and the unread tail of a row's last page are not counted, so
they show as a lower share."""
from bench import trace, window

KERNEL = "paged_decode_attn"


def read(view):
    tr = getattr(view, "trace", None)
    span = window.traced_ticks(view) if tr else None
    t_kernel = trace.kernel_seconds(tr, KERNEL) if span else 0.0
    if not t_kernel:
        return None
    cfg, pk = view.cfg, view.peaks
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    per_tick = {}
    for tick, _, keys in window.decode_rows(view, *span):
        b, f = per_tick.get(tick, (0.0, 0.0))
        per_tick[tick] = (b + 2 * (2 * keys * K * hd + 2 * H * hd),
                          f + 4.0 * keys * H * hd)
    least = sum(max(b / pk["hbm_bytes_per_s"], f / pk["bf16_flops_per_s"])
                for b, f in per_tick.values()) * cfg["n_layers"]
    return 100.0 * least / t_kernel

"""Serving model step: model FLOPs of every token the traced window
computed, prefill and decode, over the window times the chip's bf16 peak,
in %.

What a token requires is the model family's count (``serve_flops`` for a
decoded token, ``prefill_flops`` for a prompt, in ``bench/arch/<arch>.py``):
frozen projections and head, attention over the keys it attends (its
position plus one in a prompt, its context in decode), and what its
adapter needs. Padding and recomputed projections do not count."""
from bench import harness, window


def read(view):
    tr = getattr(view, "trace", None)
    span = window.traced_ticks(view) if tr else None
    if not span:
        return None
    fam = harness.family(view.cfg, getattr(view, "family", None))
    bank = window.bank_of(view)
    total = 0.0
    for _, c, keys in window.decode_rows(view, *span):
        total += fam.serve_flops(view, bank[c], keys)
    for _, c, S in window.prefill_rows(view, *span):
        total += fam.prefill_flops(view, bank[c], S)
    secs = window.traced_seconds(view)
    return 100.0 * total / (secs * view.peaks["bf16_flops_per_s"])

"""SGMV (multi-adapter LoRA) kernel: its least possible time over its
device time in the traced window, in %.

One SGMV call applies one LoRA target at one layer to one tick's batch
(decode rows, or prompt tokens in a prefill). Its necessary work is that of
the LoRA rows alone: each real LoRA token reads x (d_in) and writes y
(d_out) in bf16, and each distinct adapter in the call reads A [d_in, r]
and B [r, d_out] once, in bf16; FLOPs are ``2 * r * (d_in + d_out)`` per
token. Rows of other methods, padding rows and block padding are not
counted, so they show as a lower share. A target's widths are the model
family's (``target_dims`` in ``bench/arch/<arch>.py``)."""
from bench import harness, trace, window

KERNEL = "sgmv"


def read(view):
    tr = getattr(view, "trace", None)
    span = window.traced_ticks(view) if tr else None
    t_kernel = trace.kernel_seconds(tr, KERNEL) if span else 0.0
    if not t_kernel:
        return None
    cfg, pk = view.cfg, view.peaks
    bank = window.bank_of(view)
    calls = {}          # (tick, phase) -> {client: tokens}
    for tick, c, _ in window.decode_rows(view, *span):
        if bank[c]["method"] == "lora":
            d = calls.setdefault((tick, "decode"), {})
            d[c] = d.get(c, 0) + 1
    for tick, c, S in window.prefill_rows(view, *span):
        if bank[c]["method"] == "lora":
            d = calls.setdefault((tick, "prefill"), {})
            d[c] = d.get(c, 0) + S
    fam = harness.family(cfg, getattr(view, "family", None))
    dims = fam.target_dims(cfg)
    least = 0.0
    for toks in calls.values():
        for b in {bank[c]["name"]: bank[c] for c in toks}.values():
            mine = {c: n for c, n in toks.items() if bank[c] is b}
            n, r = sum(mine.values()), b["rank"]
            for t in b["targets"]:
                din, dout = dims[t]
                by = 2 * (n * (din + dout) + len(mine) * r * (din + dout))
                fl = 2.0 * n * r * (din + dout)
                least += max(by / pk["hbm_bytes_per_s"],
                             fl / pk["bf16_flops_per_s"])
    return 100.0 * least * cfg["n_layers"] / t_kernel

"""Which work a traced window held, from the drivers' per-request and
per-tick records (bookkeeping only; each metric reader keeps its own
operation and byte counts).

Under the engine's continuous batching every admitted request decodes one
token in every tick from its admission tick on: a request of ``n`` output
tokens admitted in tick ``a`` samples token 0 from its prefill and token
``j >= 1`` in tick ``a + j - 1``, attending ``S + j`` keys (its prompt and
the ``j`` tokens before it)."""
from __future__ import annotations


def traced_ticks(view):
    """[i0, i1): the ticks that ran wholly inside the profiler window."""
    if "tr0" not in view.snaps or "tr1" not in view.snaps:
        return None
    return view.snaps["tr0"][1], view.snaps["tr1"][1]


def traced_seconds(view) -> float:
    return view.snaps["tr1"][0] - view.snaps["tr0"][0]


def decode_rows(view, i0: int, i1: int) -> list:
    """[(tick, client, keys attended)] of every decode row in ticks
    [i0, i1)."""
    out = []
    for r in view.recs:
        if r.admit_tick is None or r.req is None or r.req.status != "ok":
            continue
        a = r.admit_tick
        lo, hi = max(1, i0 - a + 1), min(r.n - 1, i1 - a)
        for j in range(lo, hi + 1):
            out.append((a + j - 1, r.client, r.S + j))
    return out


def prefill_rows(view, i0: int, i1: int) -> list:
    """[(tick, client, prompt tokens)] of prompts prefilled in [i0, i1)."""
    return [(r.admit_tick, r.client, r.S) for r in view.recs
            if r.admit_tick is not None and i0 <= r.admit_tick < i1
            and r.req is not None and r.req.status == "ok"]


def bank_of(view) -> dict:
    """Global client id -> its bank's entry in the mix."""
    out, c = {}, 0
    for b in view.mix["banks"]:
        for _ in range(b["clients"]):
            out[c] = b
            c += 1
    return out

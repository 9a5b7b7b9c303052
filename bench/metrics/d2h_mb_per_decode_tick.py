"""Decode tick host path: what the decode step copies to the host (logits
and finite flags, the engine's ``decode_d2h_bytes`` counter) per decode
tick over the window, in MB of 1e6 bytes. Silent where the engine does
not count it."""


def read(view):
    if view is None or "t0" not in view.snaps or "t1" not in view.snaps:
        return None
    a, b = view.snaps["t0"][2], view.snaps["t1"][2]
    if "decode_d2h_bytes" not in a:
        return None
    ticks = b["ticks"] - a["ticks"]
    if ticks <= 0:
        return None
    return (b["decode_d2h_bytes"] - a["decode_d2h_bytes"]) / ticks / 1e6

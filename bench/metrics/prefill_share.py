"""Serving prefill: the share of the window's tick time spent in the
engine's ``prefill`` span (admission prefill and its host round trip), in
%. Read from the span totals at the window's two ends."""


def read(view):
    if view is None or "t0" not in view.snaps or "t1" not in view.snaps:
        return None
    a, b = view.snaps["t0"][3], view.snaps["t1"][3]
    if "tick" not in a:
        return None
    tick = b["tick"] - a["tick"]
    return 100.0 * (b["prefill"] - a["prefill"]) / tick if tick > 0 else None

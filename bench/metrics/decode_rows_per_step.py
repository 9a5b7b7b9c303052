"""Compact decode batch: real rows per decode step over the window
(``compact_rows`` over decode ticks, both engine counts)."""


def read(view):
    if view is None or "t0" not in view.snaps or "t1" not in view.snaps:
        return None
    a, b = view.snaps["t0"][2], view.snaps["t1"][2]
    steps = b["ticks"] - a["ticks"]
    return (b["compact_rows"] - a["compact_rows"]) / steps if steps else None

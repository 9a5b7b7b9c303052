"""Decode tick host path: the serving tick's seconds outside admission,
prefill and the wait for the decode step's outputs, per decode tick over
the window, in ms. That is the decode tick's host work: the row gather
(``compact_gather``), the dispatch (``jit_dispatch``), the sampler
(``sample``), the per-request bookkeeping (``scatter``) and retirement.
Read from the span totals at the window's two ends (``tick`` less
``admit``, ``prefill`` and ``device_sync``) over the engine's decode tick
count.

Silent where the engine does not time decode alone: an engine without the
``decode_d2h_bytes`` counter also times prefill's dispatch and copy as
``jit_dispatch`` and ``device_sync``, inside ``prefill``."""


def read(view):
    if view is None or "t0" not in view.snaps or "t1" not in view.snaps:
        return None
    (_, _, sa, a), (_, _, sb, b) = view.snaps["t0"], view.snaps["t1"]
    if "tick" not in a or "decode_d2h_bytes" not in sa:
        return None
    ticks = sb["ticks"] - sa["ticks"]
    if ticks <= 0:
        return None
    host = sum((b[k] - a[k]) * (1 if k == "tick" else -1)
               for k in ("tick", "admit", "prefill", "device_sync"))
    return 1e3 * host / ticks

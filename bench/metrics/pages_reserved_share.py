"""Page pool: of the pages that admission has claimed, the share promised
to requests in flight but not yet taken from the pool, over the window's
decode ticks, in %. The engine adds, at each decode tick, the pages held
by requests to ``page_ticks_held`` and the pages promised to
``page_ticks_reserved``. Silent where the engine does not count them."""


def read(view):
    if view is None or "t0" not in view.snaps or "t1" not in view.snaps:
        return None
    a, b = view.snaps["t0"][2], view.snaps["t1"][2]
    if "page_ticks_reserved" not in a:
        return None
    held = b["page_ticks_held"] - a["page_ticks_held"]
    resv = b["page_ticks_reserved"] - a["page_ticks_reserved"]
    return 100.0 * resv / (held + resv) if held + resv > 0 else None

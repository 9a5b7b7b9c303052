"""Paged decode attention: of the block-table pages of the compact decode
buckets' rows, the share in the compute blocks the kernel reads, over the
window's decode ticks, in %. The engine adds, at each compact decode tick,
the pages of the blocks visited for each row to ``attn_pages_read`` (a
padding row visits none) and the table's width for each row to
``attn_pages_table``. Silent where the engine does not count them."""


def read(view):
    if view is None or "t0" not in view.snaps or "t1" not in view.snaps:
        return None
    a, b = view.snaps["t0"][2], view.snaps["t1"][2]
    if "attn_pages_read" not in a:
        return None
    read_ = b["attn_pages_read"] - a["attn_pages_read"]
    table = b["attn_pages_table"] - a["attn_pages_table"]
    return 100.0 * read_ / table if table > 0 else None

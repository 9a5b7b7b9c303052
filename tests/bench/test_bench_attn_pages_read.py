"""The reader of the engine's attention-page counters,
``attn_pages_read_share``: its arithmetic on hand-made window ends, its
silence where the engine does not count (as an engine without these
counters reads) or no compact decode tick ran, and its report by a traced
run of a tiny serving cell."""
import math
from types import SimpleNamespace

import pytest

from benchtiny import REPO, run_cell, write_root

from bench.harness import Registry

NAME = "attn_pages_read_share"


def reader():
    return Registry([REPO]).reader(NAME)


def _view(counters=True):
    def stats(ticks, read, table):
        s = {"ticks": ticks, "decode_tokens": 0}
        if counters:
            s.update(attn_pages_read=read, attn_pages_table=table)
        return s

    return SimpleNamespace(snaps={
        "t0": (0.0, 0, stats(10, 50, 100), {}),
        "t1": (5.0, 50, stats(60, 3050, 10100), {})})


def test_arithmetic():
    # 3000 of the 10000 table pages of the window's ticks were read
    assert reader().read(_view()) == pytest.approx(100 * 3000 / 10000)


@pytest.mark.parametrize("view", ["no_counters", "no_snaps", "no_ticks"])
def test_silent(view):
    v = {"no_counters": _view(counters=False),
         "no_snaps": SimpleNamespace(snaps={}),
         "no_ticks": _view()}[view]
    if view == "no_ticks":
        v.snaps["t1"] = v.snaps["t0"]
    assert reader().read(v) is None


def test_reported_by_a_traced_tiny_run(tmp_path, capsys):
    root = write_root(tmp_path, extra_metrics=[
        {"name": NAME, "unit": "%", "workloads": ["tiny.chat"]}])
    res = run_cell(root, "tiny.chat", seed=2**31 + 9, trace=1,
                   capsys=capsys)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert math.isfinite(m[NAME]) and 0 < m[NAME] <= 100

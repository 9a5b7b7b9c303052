"""The readers of the engine's decode-tick and page-pool counters: their
arithmetic on hand-made window ends, their silence where the engine does
not count (as an engine without these counters reads), the page counters
against the allocator's lists that the open-loop serving module reads
(``open_loop.pool_pages``) at every tick, and all three reported by a
traced run of a tiny serving cell."""
import math
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from benchtiny import BANKS, REPO, TINY_MODEL, run_cell, write_root

from bench.harness import Registry

NEW = ["host_ms_per_decode_tick", "d2h_mb_per_decode_tick",
       "pages_reserved_share"]


def reader(name):
    return Registry([REPO]).reader(name)


def _view(counters=True, spans=True):
    def stats(ticks, d2h, held, resv):
        s = {"ticks": ticks, "decode_tokens": 0}
        if counters:
            s.update(decode_d2h_bytes=d2h, page_ticks_held=held,
                     page_ticks_reserved=resv)
        return s

    def sp(tick, admit, prefill, sync):
        return {"tick": tick, "admit": admit, "prefill": prefill,
                "device_sync": sync, "jit_dispatch": 0.0} if spans else {}

    return SimpleNamespace(snaps={
        "t0": (0.0, 0, stats(10, 1e6, 500, 100), sp(1.0, 0.1, 0.2, 0.3)),
        "t1": (5.0, 50, stats(60, 501e6, 30500, 10100),
               sp(6.0, 0.2, 0.7, 3.3))})


def test_arithmetic():
    v = _view()
    # 5 s of ticks, less 0.1 admit, 0.5 prefill, 3.0 device wait, per 50
    assert reader("host_ms_per_decode_tick").read(v) == pytest.approx(
        1e3 * (5.0 - 0.1 - 0.5 - 3.0) / 50)
    assert reader("d2h_mb_per_decode_tick").read(v) == pytest.approx(10.0)
    assert reader("pages_reserved_share").read(v) == pytest.approx(
        100 * 10000 / 40000)


def test_silent_without_the_engine_counters():
    for name in NEW:
        assert reader(name).read(_view(counters=False)) is None
        assert reader(name).read(SimpleNamespace(snaps={})) is None
    # untraced engines keep no spans: the span reader is silent, the
    # counter readers are not
    assert reader("host_ms_per_decode_tick").read(_view(spans=False)) is None
    assert reader("pages_reserved_share").read(_view(spans=False)) > 0


def test_page_counters_match_the_allocator_lists_every_tick():
    from repro.config import AdapterConfig, ModelConfig, ServeConfig
    from repro.core import adapters as ad_lib
    from repro.models import get_model
    from repro.serving.engine import Request, ServingEngine
    pool_pages = Registry([REPO]).kind("open_loop").pool_pages
    cfg = ModelConfig(**{k: v for k, v in TINY_MODEL.items()
                         if k != "source"})
    acfgs = [AdapterConfig(method=b["method"], rank=b["rank"] or 8,
                           alpha=b["alpha"] or 16.0,
                           targets=tuple(b["targets"]),
                           n_prefix=b["n_prefix"] or 16) for b in BANKS]
    base = get_model(cfg).init_params(jax.random.PRNGKey(0))
    banks = [ad_lib.init_client_bank(cfg, a, 2, jax.random.PRNGKey(i + 1))
             for i, a in enumerate(acfgs)]
    eng = ServingEngine(cfg, acfgs, ServeConfig(
        n_clients=6, max_seq=64, page_block=8, pool_pages=12), base, banks,
        max_batch_per_client=2)
    rng = np.random.default_rng(0)
    for i in range(14):
        eng.submit(Request(client_id=i % 6, prompt=rng.integers(
            0, cfg.vocab, (1, int(rng.integers(4, 30)))).astype(np.int32),
            max_new_tokens=int(rng.integers(2, 20)), arrive_tick=i))
    steps = 0
    while eng.pending():
        before = dict(eng.stats)
        eng.service_tick()
        _, held, promised = pool_pages(eng)
        if eng.stats["ticks"] > before["ticks"]:
            steps += 1
            assert eng.stats["page_ticks_held"] - \
                before["page_ticks_held"] == held
            assert eng.stats["page_ticks_reserved"] - \
                before["page_ticks_reserved"] == promised - held
    assert steps > 0 and eng.stats["page_ticks_reserved"] > 0


def test_reported_by_a_traced_tiny_run(tmp_path, capsys):
    root = write_root(tmp_path, extra_metrics=[
        {"name": n, "unit": "x", "workloads": ["tiny.chat"]} for n in NEW])
    res = run_cell(root, "tiny.chat", seed=2**31 + 9, trace=1,
                   capsys=capsys)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW) <= set(m)
    assert all(math.isfinite(m[k]) and m[k] > 0 for k in NEW)
    # float32 logits of the tiny vocabulary, 4 bytes each, at least one
    # 4-row bucket per decode tick, plus one flag per row
    assert m["d2h_mb_per_decode_tick"] >= 4 * (4 * 256 + 1) / 1e6
    assert m["pages_reserved_share"] < 100

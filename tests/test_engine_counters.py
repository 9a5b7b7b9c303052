"""The engines' always-on counters and the names their programs carry.

- ``decode_d2h_bytes`` / ``prefill_d2h_bytes``: what the decode step and
  the prefill copy to the host, counted from the arrays themselves;
- ``page_ticks_held`` / ``page_ticks_reserved``: the page pool's
  occupancy, added at every decode tick;
- the decode tick's host phases: ``sample`` once per tick, and the
  decode-only spans (``jit_dispatch``, ``device_sync``) no longer entered
  by prefill, which has ``prefill_dispatch`` and ``prefill_sync``;
- the compact serving programs lower as ``jit_compact_decode`` and
  ``jit_compact_prefill``, the fine-tuning banks as ``jit_train_<method>``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import AdapterConfig, ServeConfig
from repro.core import adapters as ad_lib
from repro.core import symbiosis
from repro.models import get_model
from repro.obs import Obs
from repro.serving import engine as serving_engine
from repro.serving.engine import Request, SamplingParams, ServingEngine
from repro.training import engine as training_engine
from conftest import tiny

METHODS = [
    AdapterConfig(method="lora", rank=4, alpha=8.0, targets=("q", "v")),
    AdapterConfig(method="ia3", targets=("k", "v", "down")),
    AdapterConfig(method="prefix", targets=("q", "v"), n_prefix=4),
]


def _engine(obs=None, pool_pages=10, max_seq=48):
    cfg = tiny()
    scfg = ServeConfig(n_clients=6, max_seq=max_seq, page_block=8,
                       pool_pages=pool_pages)
    base = get_model(cfg).init_params(jax.random.PRNGKey(0))
    banks = [ad_lib.init_client_bank(cfg, a, 2, jax.random.PRNGKey(5 + i))
             for i, a in enumerate(METHODS)]
    return ServingEngine(cfg, METHODS, scfg, base, banks,
                         max_batch_per_client=2, obs=obs)


def _requests(vocab, n=10, seed=0, prompt=(5, 20), new=(2, 12)):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        sp = SamplingParams("temperature", 0.8, seed=i) if i % 3 == 0 \
            else None
        out.append(Request(client_id=i % 6,
                           prompt=rng.integers(0, vocab, (1, int(
                               rng.integers(*prompt)))).astype(np.int32),
                           max_new_tokens=int(rng.integers(*new)),
                           sampling=sp, arrive_tick=i // 2))
    return out


def _pool(eng):
    """(held, held or promised) as read from the allocator's lists."""
    total = eng.n_clients * eng._pool_pages
    held = total - sum(len(f) for f in eng._free_pages)
    return held, held + sum(eng._reserved)


def _drive(eng, reqs):
    """Serve to the end, one tick at a time; per tick: the stats before and
    after, and the allocator's reading after."""
    for r in reqs:
        eng.submit(r)
    ticks = []
    while eng.pending():
        before = dict(eng.stats)
        eng.service_tick()
        ticks.append((before, dict(eng.stats), _pool(eng)))
    return ticks


def test_d2h_bytes_and_page_ticks_per_tick():
    eng = _engine()
    vocab = eng.cfg.vocab
    ticks = _drive(eng, _requests(vocab))
    decoded = 0
    for before, after, (held, promised) in ticks:
        d = {k: after[k] - before[k] for k in after}
        if d["ticks"]:
            decoded += 1
            nb = d["compact_rows"] + d["compact_padded"]
            # float32 logits [nb, V] and one bool flag per row
            assert d["decode_d2h_bytes"] == nb * vocab * 4 + nb
            assert d["page_ticks_held"] == held
            assert d["page_ticks_reserved"] == promised - held
        else:
            assert d["decode_d2h_bytes"] == 0
            assert d["page_ticks_held"] == d["page_ticks_reserved"] == 0
        nb_pre = d["compact_prefill_rows"] + d["compact_prefill_padded"]
        assert d["prefill_d2h_bytes"] == nb_pre * vocab * 4
    assert decoded == eng.stats["ticks"] > 0
    assert eng.stats["page_ticks_reserved"] > 0
    assert eng.stats["prefill_d2h_bytes"] > 0


def test_attn_pages_counted_per_compact_tick():
    """Each compact decode tick adds, for each real row, the pages of the
    128-token compute blocks (16 pages of 8 tokens) up to the position of
    the row's token, at most the table's 40; a padding row adds none. The
    table count is the bucket's rows times 40. The positions are the ones
    the device holds for the rows when the step runs."""
    eng = _engine(pool_pages=48, max_seq=320)
    seen = []
    inner = eng._decode_tick_compact

    def spy(serve):
        rows = [(c, s) for c in sorted(serve) for s in eng._active_slots[c]]
        pos = [int(eng._wpos[c, s]) - 1 for c, s in rows]
        assert pos == [int(eng.caches["pos"][c, s]) for c, s in rows]
        seen.append(pos)
        return inner(serve)

    eng._decode_tick_compact = spy
    ticks = _drive(eng, _requests(eng.cfg.vocab, n=10, prompt=(60, 280),
                                  new=(10, 40)))
    decoded = [(b, a) for b, a, _ in ticks if a["ticks"] > b["ticks"]]
    assert len(decoded) == len(seen) > 0
    for (before, after), pos in zip(decoded, seen):
        d = {k: after[k] - before[k] for k in after}
        assert d["attn_pages_read"] == sum(min(40, 16 * (p // 128 + 1))
                                           for p in pos)
        assert d["attn_pages_table"] == 40 * (d["compact_rows"]
                                              + d["compact_padded"])
    # rows in the first block, in the second, and in the third, which
    # holds only the table's last 8 columns
    flat = [p for pos in seen for p in pos]
    assert min(flat) < 128 and any(128 <= p < 256 for p in flat)
    assert max(flat) >= 256


def test_decode_spans_are_decode_only():
    obs = Obs()
    eng = _engine(obs)
    _drive(eng, _requests(eng.cfg.vocab))

    def n(phase):
        return obs.metrics.histogram("span_seconds", phase=phase).n

    st = eng.stats
    assert n("sample") == n("scatter") == st["ticks"]
    assert n("jit_dispatch") == n("device_sync") == st["ticks"]
    assert n("prefill_dispatch") == n("prefill_sync") == \
        n("prefill_compact_gather") == st["compact_prefill_batches"] > 0
    # every tick has its record, by phase, with what no phase covers
    recs = list(obs.ticks.records)
    assert len(recs) == obs.metrics.histogram(
        "tick_seconds", engine="serving").n
    for r in recs:
        covered = sum(v for k, v in r["phases"].items()
                      if k in ("admit", "prefill", "compact_gather",
                               "jit_dispatch", "device_sync", "sample",
                               "scatter"))
        assert r["seconds"] == pytest.approx(covered + r["uncovered"],
                                             abs=1e-9)
    longest = obs.ticks.longest(2)
    assert [r["seconds"] for r in longest] == \
        sorted((r["seconds"] for r in recs), reverse=True)[:2]


def test_obs_on_off_bitwise_with_sampling():
    out = {}
    for tag, obs in (("off", None), ("on", Obs())):
        eng = _engine(obs)
        reqs = _requests(eng.cfg.vocab, seed=3)
        _drive(eng, reqs)
        out[tag] = [r.generated for r in reqs]
        out[tag + "_stats"] = dict(eng.stats)
    for a, b in zip(out["off"], out["on"]):
        np.testing.assert_array_equal(a, b)
    assert out["off_stats"] == out["on_stats"]


def test_compact_programs_lower_under_their_names():
    eng = _engine()
    eng._sync_tbl()
    z = jnp.zeros((4,), jnp.int32)
    no = jnp.zeros((4,), bool)
    dec = eng._compact_step.lower(eng.base, tuple(eng.banks), eng.caches,
                                  z, z, z, z, z, no).as_text()
    assert dec.startswith("module @jit_compact_decode ")
    pre = serving_engine._jit_compact_prefill(
        eng.cfg, eng.bank_cfgs, eng.scfg, None, 0)
    txt = pre.lower(eng.base, tuple(eng.banks), eng.caches,
                    jnp.zeros((4, 8), jnp.int32), z, z, z, z, z, z,
                    no).as_text()
    assert txt.startswith("module @jit_compact_prefill ")
    # a sharded engine's pinned wrapper keeps the name
    pinned = serving_engine._pin_serving(
        symbiosis.make_compact_decode_step(eng.cfg, eng.bank_cfgs, eng.scfg),
        eng.cfg, eng.scfg, mesh=object())
    assert pinned.__name__ == "compact_decode"
    assert symbiosis.make_page_copy(eng.cfg, eng.scfg).__name__ == \
        "copy_page"


@pytest.mark.parametrize("acfg,name", [
    (AdapterConfig(method="lora", rank=16), "train_lora_r16"),
    (AdapterConfig(method="lora", rank=64, targets=("q", "k", "v", "o")),
     "train_lora_r64"),
    (AdapterConfig(method="ia3", targets=("k", "v", "down")), "train_ia3"),
    (AdapterConfig(method="prefix", n_prefix=32), "train_prefix"),
])
def test_train_bank_program_names(acfg, name):
    cfg = tiny()
    step = symbiosis.make_compact_train_step(cfg, acfg)
    assert step.__name__ == name
    assert training_engine._pin_train(step, cfg, mesh=object()).__name__ \
        == name
    jitted = training_engine._jit_compact_train(cfg, acfg, 0, True, True)
    assert jitted.__name__ == name

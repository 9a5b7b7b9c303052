"""Dynamic serving-bank admission (ISSUE 6 satellite).

``admit_bank`` must grow a live engine without perturbing anyone: a client
admitted later generates byte-identically to the same client present from
construction, existing clients are untouched, a NEW AdapterConfig converts
a single-method engine into the mixed registry, the router is charged at
admission and released at retirement, and retired clients are refused.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.common.hardware import V5E
from repro.config import AdapterConfig, ServeConfig, DENSE
from repro.core import adapters as ad_lib
from repro.core import symbiosis
from repro.serving.engine import Request, ServingEngine
from conftest import tiny

LORA = AdapterConfig(method="lora", rank=4, alpha=8.0, targets=("q", "v"))
IA3 = AdapterConfig(method="ia3", targets=("k", "v", "down"))


def _prompts(cfg, n, rng):
    return [rng.integers(1, cfg.vocab, (1, 5 + i)).astype(np.int32)
            for i in range(n)]


def _serve_all(eng, prompts, clients, max_new=4):
    reqs = [Request(client_id=c, prompt=p, max_new_tokens=max_new)
            for c, p in zip(clients, prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return [r.generated for r in reqs]


def test_admitted_client_matches_static_engine():
    cfg = tiny(DENSE)
    base = None
    key = jax.random.PRNGKey(0)
    base, bank3, _ = symbiosis.init_system(cfg, LORA, 3, key)
    rng = np.random.default_rng(1)
    prompts = _prompts(cfg, 3, rng)

    # engine A: all three clients from construction
    eng_a = ServingEngine(cfg, LORA, ServeConfig(n_clients=3, max_seq=32,
                                                 page_block=8),
                          base, bank3, max_batch_per_client=1)
    gen_a = _serve_all(eng_a, prompts, [0, 1, 2])

    # engine B: two clients, then client 2's adapter admitted live
    bank2 = jax.tree.map(lambda x: x[:2], bank3)
    eng_b = ServingEngine(cfg, LORA, ServeConfig(n_clients=2, max_seq=32,
                                                 page_block=8),
                          base, bank2, max_batch_per_client=1)
    gen_b01 = _serve_all(eng_b, prompts[:2], [0, 1])
    adm = eng_b.admit_bank(LORA, jax.tree.map(lambda x: x[2:3], bank3))
    assert adm.client_ids == [2]
    assert eng_b.n_clients == 3
    (gen_b2,) = _serve_all(eng_b, prompts[2:], adm.client_ids)

    for a, b in zip(gen_a[:2], gen_b01):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(gen_a[2], gen_b2)


def test_admit_new_method_converts_to_mixed():
    cfg = tiny(DENSE)
    base, bank_l, _ = symbiosis.init_system(cfg, LORA, 2, jax.random.PRNGKey(0))
    bank_i = ad_lib.init_client_bank(cfg, IA3, 1, jax.random.PRNGKey(7))
    rng = np.random.default_rng(2)
    prompts = _prompts(cfg, 3, rng)

    # grown engine: lora-only, then an IA3 bank admitted live
    eng = ServingEngine(cfg, LORA, ServeConfig(n_clients=2, max_seq=32,
                                               page_block=8),
                        base, bank_l, max_batch_per_client=1)
    adm = eng.admit_bank(IA3, bank_i)
    assert adm.client_ids == [2]
    gen = _serve_all(eng, prompts, [0, 1, 2])

    # reference: the mixed registry from construction
    eng_m = ServingEngine(cfg, (LORA, IA3),
                          ServeConfig(n_clients=3, max_seq=32, page_block=8),
                          base, (bank_l, bank_i), max_batch_per_client=1)
    gen_m = _serve_all(eng_m, prompts, [0, 1, 2])
    for a, b in zip(gen, gen_m):
        np.testing.assert_array_equal(a, b)


def test_grow_existing_bank_existing_clients_untouched():
    cfg = tiny(DENSE)
    base, bank3, _ = symbiosis.init_system(cfg, LORA, 3, jax.random.PRNGKey(3))
    bank2 = jax.tree.map(lambda x: x[:2], bank3)
    eng = ServingEngine(cfg, LORA, ServeConfig(n_clients=2, max_seq=32,
                                               page_block=8),
                        base, bank2, max_batch_per_client=1)
    before = jax.tree.map(lambda x: np.asarray(x).copy(), eng.caches)
    eng.admit_bank(LORA, jax.tree.map(lambda x: x[2:3], bank3))
    page_axes = symbiosis.cache_page_axes(
        cfg, 32, **symbiosis.serve_cache_kwargs(
            cfg, ServeConfig(n_clients=2, max_seq=32, page_block=8)))

    def _old_region(new, old, pax):
        ax = 0 if pax is None else pax
        return np.take(np.asarray(new), np.arange(old.shape[ax]), axis=ax)

    # existing clients' cache state (per-slot leaves AND their page ranges)
    # is byte-identical after growth
    jax.tree.map(
        lambda old, new, pax: np.testing.assert_array_equal(
            _old_region(new, old, pax), old),
        before, eng.caches, page_axes)


def test_retired_clients_are_refused():
    cfg = tiny(DENSE)
    base, bank, _ = symbiosis.init_system(cfg, LORA, 1, jax.random.PRNGKey(4))
    eng = ServingEngine(cfg, LORA, ServeConfig(n_clients=1, max_seq=32,
                                               page_block=8),
                        base, bank, max_batch_per_client=1)
    extra = ad_lib.init_client_bank(cfg, LORA, 1, jax.random.PRNGKey(5))
    adm = eng.admit_bank(LORA, extra)
    prompt = np.ones((1, 5), np.int32)
    _serve_all(eng, [prompt], adm.client_ids)
    eng.retire_bank(adm)
    with pytest.raises(ValueError, match="retired"):
        eng.submit(Request(client_id=adm.client_ids[0], prompt=prompt))


def test_retire_refuses_busy_clients_and_router_roundtrip():
    from repro.serving.router import PlacementRouter, Slot

    cfg = tiny(DENSE)
    base, bank, _ = symbiosis.init_system(cfg, LORA, 1, jax.random.PRNGKey(6))
    router = PlacementRouter(cfg, [Slot(0, free_hbm=1e9)], chip=V5E, host_free_bytes=0)
    eng = ServingEngine(cfg, LORA, ServeConfig(n_clients=1, max_seq=32,
                                               page_block=8),
                        base, bank, max_batch_per_client=1, router=router)
    free0 = router.slots[0].free_hbm
    extra = ad_lib.init_client_bank(cfg, LORA, 1, jax.random.PRNGKey(8))
    adm = eng.admit_bank(LORA, extra)
    assert router.slots[0].free_hbm < free0      # charged at admission
    eng.submit(Request(client_id=adm.client_ids[0],
                       prompt=np.ones((1, 5), np.int32), max_new_tokens=8))
    eng.service_tick()                           # request now in flight
    with pytest.raises(RuntimeError, match="in flight"):
        eng.retire_bank(adm)
    eng.run()
    eng.retire_bank(adm)
    assert router.slots[0].free_hbm == free0     # released at retirement


def test_admission_requires_paged_compact():
    cfg = tiny(DENSE)
    base, bank, _ = symbiosis.init_system(cfg, LORA, 1, jax.random.PRNGKey(9))
    eng = ServingEngine(cfg, LORA, ServeConfig(n_clients=1, max_seq=32),
                        base, bank, max_batch_per_client=1)
    extra = ad_lib.init_client_bank(cfg, LORA, 1, jax.random.PRNGKey(10))
    with pytest.raises(ValueError, match="paged"):
        eng.admit_bank(LORA, extra)

"""Mixed-PEFT serving banks (ISSUE 5 tentpole).

One ServingEngine holds several banks keyed by AdapterConfig — LoRA, IA3
and prefix clients served CONCURRENTLY over one frozen base — and a single
compacted decode tick carries per-row methods. The contract: every
client's output in a mixed batch is BYTE-identical to serving that client
alone through a single-method engine (its "solo single-method run"),
across tick policies, occupancies and mid-stream churn.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.common.hardware import V5E
from repro.config import AdapterConfig, ServeConfig, DENSE, MOE, VLM, HYBRID, ENCDEC
from repro.core import adapters as ad_lib
from repro.models import get_model
from repro.serving.engine import ServingEngine, Request
from repro.serving.router import PlacementRouter, Slot
from repro.serving import kvcache
from conftest import tiny

METHOD_CFGS = [
    AdapterConfig(method="lora", rank=4, alpha=8.0, targets=("q", "v")),
    AdapterConfig(method="ia3", targets=("k", "v", "down")),
    AdapterConfig(method="prefix", targets=("q", "v"), n_prefix=4),
]


def _system(arch=DENSE, clients_per_bank=1, seed=0, page_block=8, max_seq=48):
    cfg = tiny(arch)
    scfg = ServeConfig(n_clients=3 * clients_per_bank, max_seq=max_seq,
                      page_block=page_block)
    base = get_model(cfg).init_params(jax.random.PRNGKey(seed))
    banks = [ad_lib.init_client_bank(cfg, a, clients_per_bank,
                                     jax.random.PRNGKey(seed + 5 + i))
             for i, a in enumerate(METHOD_CFGS)]
    return cfg, scfg, base, banks


def _solo_reference(cfg, scfg, base, banks, req, max_b):
    """Serve one request alone through a fresh SINGLE-method engine holding
    only that client's adapter — the byte-identity oracle."""
    cpb = jax.tree.leaves(banks[0])[0].shape[0]    # clients per bank
    m, local = req.client_id // cpb, req.client_id % cpb
    one_bank = jax.tree.map(lambda x: x[local:local + 1], banks[m])
    scfg_solo = dataclasses.replace(scfg, n_clients=1)
    eng = ServingEngine(cfg, METHOD_CFGS[m], scfg_solo, base, one_bank,
                        max_batch_per_client=max_b)
    solo = Request(client_id=0, prompt=req.prompt.copy(),
                   max_new_tokens=req.max_new_tokens, sampling=req.sampling)
    eng.submit(solo)
    (done,) = eng.run()
    return done.generated


class TestMixedMethodEngine:
    """Engine-level byte-identity of mixed batches to solo runs."""

    # one client per bank (3 clients, global ids 0=lora 1=ia3 2=prefix);
    # occupancies over a 3-client x 2-slot bank
    OCCUPANCIES = {
        "one_slot": [(1, 1, 5, 6, 0)],                       # a lone IA3 row
        "bucket_boundary": [(0, 2, 5, 6, 0), (1, 2, 6, 6, 0)],   # 4 rows
        "full_bank": [(c, 2, 4 + c, 6, 0) for c in range(3)],    # 6 rows
        "churn": [(0, 1, 4, 3, 0), (1, 2, 5, 8, 1), (2, 1, 5, 4, 2),
                  (0, 1, 6, 2, 3), (2, 2, 4, 5, 6)],
    }

    def _reqs(self, cfg, rng, spec):
        return [Request(client_id=c,
                        prompt=rng.integers(0, cfg.vocab, (rows, S)).astype(np.int32),
                        max_new_tokens=new, arrive_tick=at)
                for (c, rows, S, new, at) in spec]

    def _serve_mixed(self, cfg, scfg, base, banks, reqs, *, policy, max_b=2):
        eng = ServingEngine(cfg, METHOD_CFGS, scfg, base, banks,
                            max_batch_per_client=max_b, policy=policy)
        for r in reqs:
            eng.submit(r)
        return eng, eng.run()

    @pytest.mark.parametrize("occupancy", list(OCCUPANCIES))
    def test_mixed_matches_solo(self, occupancy):
        self._case(occupancy, "opportunistic")

    @pytest.mark.tier2
    @pytest.mark.parametrize("policy", ["lockstep", "nolockstep"])
    @pytest.mark.parametrize("occupancy", list(OCCUPANCIES))
    def test_mixed_matches_solo_policies(self, occupancy, policy):
        self._case(occupancy, policy)

    def _case(self, occupancy, policy, arch=DENSE):
        cfg, scfg, base, banks = _system(arch)
        rng = np.random.default_rng(11)
        reqs = self._reqs(cfg, rng, self.OCCUPANCIES[occupancy])
        eng, done = self._serve_mixed(cfg, scfg, base, banks, reqs,
                                      policy=policy)
        assert len(done) == len(reqs)
        # one tick carried several methods whenever >1 bank was active
        for r in done:
            ref = _solo_reference(cfg, scfg, base, banks, r, 2)
            np.testing.assert_array_equal(
                r.generated, ref,
                err_msg=f"{occupancy}/{policy}: client {r.client_id} "
                        f"(method {METHOD_CFGS[r.client_id].method}) "
                        f"diverged from its solo single-method run")
        # allocator + activity state drained clean
        assert not any(eng._active_slots)
        assert not eng._active_mask.any()

    def test_three_methods_share_one_tick(self):
        """All three banks decode in the SAME compacted tick (not routed to
        per-bank ticks): with one request per bank all due at tick 0, every
        decode tick gathers 3 rows of 3 different methods."""
        cfg, scfg, base, banks = _system()
        rng = np.random.default_rng(3)
        eng = ServingEngine(cfg, METHOD_CFGS, scfg, base, banks,
                            max_batch_per_client=2)
        for c in range(3):
            eng.submit(Request(client_id=c,
                               prompt=rng.integers(0, cfg.vocab, (1, 5)).astype(np.int32),
                               max_new_tokens=5))
        done = eng.run()
        assert len(done) == 3
        # 3 active rows per decode tick, 4 ticks (first token from prefill)
        assert eng.stats["compact_rows"] == 3 * 4
        assert eng.stats["ticks"] == 4

    def test_mixed_requires_paged_layout(self):
        cfg, scfg, base, banks = _system()
        dense_scfg = dataclasses.replace(scfg, page_block=0)
        with pytest.raises(ValueError, match="paged"):
            ServingEngine(cfg, METHOD_CFGS, dense_scfg, base, banks)
        with pytest.raises(ValueError, match="compacted"):
            ServingEngine(cfg, METHOD_CFGS, scfg, base, banks,
                          compact_decode=False)

    def test_router_charges_each_bank(self):
        """An attached router is charged every bank's resident adapter
        bytes at construction and refunded by release_banks()."""
        cfg, scfg, base, banks = _system()
        bank_bytes = [ad_lib.adapter_bytes(cfg, a)[1] for a in METHOD_CFGS]
        budget = kvcache.cache_bytes(cfg, scfg.max_seq, 6) + sum(bank_bytes) * 2
        router = PlacementRouter(cfg, [Slot(0, free_hbm=budget)],
                                 chip=V5E, host_free_bytes=0)
        eng = ServingEngine(cfg, METHOD_CFGS, scfg, base, banks,
                            max_batch_per_client=2, router=router)
        assert len(eng._bank_placements) == 3
        assert router.slots[0].free_hbm == pytest.approx(
            budget - sum(bank_bytes))
        eng.release_banks()
        assert router.slots[0].free_hbm == pytest.approx(budget)

    def test_failed_bank_charge_refunds_committed_banks(self):
        """If a later bank's route_bank charge doesn't fit, the charges
        already committed for earlier banks are refunded — a failed engine
        construction must not leak router capacity."""
        cfg, scfg, base, banks = _system()
        bank_bytes = [ad_lib.adapter_bytes(cfg, a)[1] for a in METHOD_CFGS]
        budget = sum(bank_bytes[:2]) + bank_bytes[2] * 0.5   # 3rd won't fit
        router = PlacementRouter(cfg, [Slot(0, free_hbm=budget)],
                                 chip=V5E, host_free_bytes=0)
        with pytest.raises(RuntimeError, match="serving-bank"):
            ServingEngine(cfg, METHOD_CFGS, scfg, base, banks,
                          max_batch_per_client=2, router=router)
        assert router.slots[0].free_hbm == pytest.approx(budget)

    def test_mixed_rank_lora_banks(self):
        """Two LoRA banks with different ranks are separate banks in one
        engine (heterogeneity isn't only across methods)."""
        cfg = tiny(DENSE)
        scfg = ServeConfig(n_clients=2, max_seq=48, page_block=8)
        base = get_model(cfg).init_params(jax.random.PRNGKey(0))
        acfgs = [AdapterConfig(method="lora", rank=2, alpha=4.0, targets=("q", "v")),
                 AdapterConfig(method="lora", rank=8, alpha=16.0, targets=("q", "v"))]
        banks = [ad_lib.init_client_bank(cfg, a, 1, jax.random.PRNGKey(7 + i))
                 for i, a in enumerate(acfgs)]
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, cfg.vocab, (1, 6)).astype(np.int32)
                   for _ in range(2)]
        eng = ServingEngine(cfg, acfgs, scfg, base, banks,
                            max_batch_per_client=2)
        for c in range(2):
            eng.submit(Request(client_id=c, prompt=prompts[c].copy(),
                               max_new_tokens=5))
        done = {r.client_id: r for r in eng.run()}
        for c in range(2):
            solo = ServingEngine(cfg, acfgs[c],
                                 dataclasses.replace(scfg, n_clients=1),
                                 base, banks[c], max_batch_per_client=2)
            solo.submit(Request(client_id=0, prompt=prompts[c].copy(),
                                max_new_tokens=5))
            (ref,) = solo.run()
            np.testing.assert_array_equal(done[c].generated, ref.generated)


@pytest.mark.tier2
@pytest.mark.parametrize("arch", [DENSE, MOE, VLM, HYBRID, ENCDEC])
@pytest.mark.parametrize("policy", ["opportunistic", "lockstep"])
def test_mixed_method_family_sweep(arch, policy):
    """CI tier-2 sweep (ISSUE 5 satellite): methods x families x policies.
    Every family serves a lora+ia3+prefix mix in one engine; every client
    matches its solo single-method run byte-for-byte. (Enc-dec requests
    carry no frames through the engine Request type yet — the engine path
    uses zero frames for both mixed and solo, which keeps the comparison
    valid.)"""
    if arch == ENCDEC:
        pytest.skip("Request carries tokens only; enc-dec needs frame "
                    "extras threaded through the engine (ROADMAP item)")
    cfg, scfg, base, banks = _system(arch)
    rng = np.random.default_rng(13)
    reqs = [Request(client_id=c,
                    prompt=rng.integers(0, cfg.vocab, (1, 4 + c)).astype(np.int32),
                    max_new_tokens=4 + c % 2, arrive_tick=c)
            for c in range(3)]
    eng = ServingEngine(cfg, METHOD_CFGS, scfg, base, banks,
                        max_batch_per_client=2, policy=policy)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 3
    for r in done:
        ref = _solo_reference(cfg, scfg, base, banks, r, 2)
        np.testing.assert_array_equal(
            r.generated, ref,
            err_msg=f"{arch}/{policy}: client {r.client_id} diverged")

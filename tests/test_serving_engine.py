"""Multi-tenant serving engine behaviour."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.hardware import V5E
from repro.config import ServeConfig, DENSE
from repro.core import symbiosis
from repro.serving.engine import ServingEngine, Request, SamplingParams
from repro.serving import kvcache
from repro.serving.router import PlacementRouter, Slot
from conftest import tiny


def _solo_reference(cfg, scfg, base, bank, lora_cfg, req, max_b):
    """Serve one request alone through a fresh engine — the baseline the
    paper's exactness claim compares against."""
    eng = ServingEngine(cfg, lora_cfg, scfg, base, bank,
                        max_batch_per_client=max_b)
    solo = Request(client_id=req.client_id, prompt=req.prompt.copy(),
                   max_new_tokens=req.max_new_tokens,
                   sampling=req.sampling)
    eng.submit(solo)
    (done,) = eng.run()
    return done.generated


@pytest.fixture
def system(key, lora_cfg):
    cfg = tiny(DENSE)
    scfg = ServeConfig(n_clients=3, max_seq=48)
    base, bank, _ = symbiosis.init_system(cfg, lora_cfg, 3, key)
    return cfg, scfg, base, bank


class TestEngine:
    def test_generation_matches_direct_decode(self, system, lora_cfg):
        """Engine outputs == a hand-rolled prefill+decode loop for the same
        client (batching across clients must not change results — the
        paper's exactness claim at the serving layer)."""
        cfg, scfg, base, bank = system
        eng = ServingEngine(cfg, lora_cfg, scfg, base, bank, max_batch_per_client=2)
        rng = np.random.default_rng(0)
        prompts = {c: rng.integers(0, cfg.vocab, (2, 8)).astype(np.int32)
                   for c in range(3)}
        for c in range(3):
            eng.submit(Request(client_id=c, prompt=prompts[c], max_new_tokens=5))
        done = {r.client_id: r for r in eng.run()}

        # direct single-client reference
        from repro.models import get_model
        from repro.core.virtlayer import make_client_ctx
        model = get_model(cfg)
        ctx = make_client_ctx(cfg, lora_cfg)
        for c in range(3):
            adapter = jax.tree.map(lambda x, c=c: x[c], bank)
            cache = model.init_cache(2, scfg.max_seq)
            logits, cache = model.prefill(base, {"tokens": jnp.asarray(prompts[c])},
                                          cache, ctx, adapter)
            toks = [np.asarray(jnp.argmax(logits, -1), np.int32)]
            for _ in range(4):
                lg, cache = model.decode_step(base, cache,
                                              jnp.asarray(toks[-1]), ctx, adapter)
                toks.append(np.asarray(jnp.argmax(lg, -1), np.int32))
            ref = np.stack(toks, axis=1)
            np.testing.assert_array_equal(done[c].generated, ref,
                                          err_msg=f"client {c} diverged")

    def test_clients_at_different_rates(self, system, lora_cfg):
        """Client independence: different max_new_tokens finish independently."""
        cfg, scfg, base, bank = system
        eng = ServingEngine(cfg, lora_cfg, scfg, base, bank, max_batch_per_client=1)
        rng = np.random.default_rng(1)
        eng.submit(Request(0, rng.integers(0, cfg.vocab, (1, 4)).astype(np.int32),
                           max_new_tokens=2))
        eng.submit(Request(1, rng.integers(0, cfg.vocab, (1, 4)).astype(np.int32),
                           max_new_tokens=9))
        done = eng.run()
        assert {r.generated.shape[1] for r in done} == {2, 9}


class TestContinuousBatching:
    def _workload(self, cfg, rng, *, n=6, rows=1, max_new=(3, 9)):
        reqs = []
        for i in range(n):
            reqs.append(Request(
                client_id=i % 3,
                prompt=rng.integers(0, cfg.vocab, (rows, 4 + 2 * (i % 3))).astype(np.int32),
                max_new_tokens=max_new[i % len(max_new)],
                arrive_tick=2 * i,          # staggered: joins mid-stream
            ))
        return reqs

    @pytest.mark.parametrize("policy", ["lockstep", "nolockstep", "opportunistic"])
    def test_staggered_arrivals_policy_invariant(self, system, lora_cfg, policy):
        """Continuous batching with staggered arrivals produces byte-identical
        greedy outputs to serving each request alone, under every policy —
        the paper's exact-output property at the serving layer."""
        cfg, scfg, base, bank = system
        rng = np.random.default_rng(7)
        reqs = self._workload(cfg, rng)
        eng = ServingEngine(cfg, lora_cfg, scfg, base, bank,
                            max_batch_per_client=2, policy=policy)
        for r in reqs:
            eng.submit(r)
        done = eng.run()
        assert len(done) == len(reqs)
        for r in done:
            ref = _solo_reference(cfg, scfg, base, bank, lora_cfg, r, 2)
            np.testing.assert_array_equal(
                r.generated, ref,
                err_msg=f"policy={policy} client {r.client_id} diverged from solo")

    def test_slot_reuse_midstream(self, system, lora_cfg):
        """More requests than slots: a finishing sequence's slot is re-admitted
        from the queue while other sequences keep decoding, and every
        occupant's output still matches solo serving."""
        cfg, scfg, base, bank = system
        rng = np.random.default_rng(3)
        # 5 requests for ONE client with 2 slots -> forced slot turnover,
        # plus a long-running request on another client that spans it all.
        reqs = [Request(client_id=0,
                        prompt=rng.integers(0, cfg.vocab, (1, 4 + i)).astype(np.int32),
                        max_new_tokens=2 + i)
                for i in range(5)]
        reqs.append(Request(client_id=1,
                            prompt=rng.integers(0, cfg.vocab, (2, 6)).astype(np.int32),
                            max_new_tokens=16))
        eng = ServingEngine(cfg, lora_cfg, scfg, base, bank,
                            max_batch_per_client=2)
        for r in reqs:
            eng.submit(r)
        done = eng.run()
        assert len(done) == 6
        # with 2 slots and 5 queued client-0 requests there must be overlap
        assert eng.stats["batched_clients"] > eng.stats["ticks"]
        for r in done:
            ref = _solo_reference(cfg, scfg, base, bank, lora_cfg, r, 2)
            np.testing.assert_array_equal(r.generated, ref)

    def test_sampling_schedule_invariant(self, system, lora_cfg):
        """Seeded temperature/top-k sampling draws depend only on the
        request's own stream -> identical under different policies."""
        cfg, scfg, base, bank = system
        rng = np.random.default_rng(11)
        outs = {}
        for policy in ("opportunistic", "nolockstep"):
            reqs = [Request(client_id=c,
                            prompt=rng.integers(0, cfg.vocab, (1, 6)).astype(np.int32),
                            max_new_tokens=6,
                            sampling=SamplingParams(method=m, temperature=0.8,
                                                    top_k=8, seed=17 + c))
                    for c, m in [(0, "temperature"), (1, "top_k"), (2, "greedy")]]
            rng = np.random.default_rng(11)    # same prompts per policy
            eng = ServingEngine(cfg, lora_cfg, scfg, base, bank,
                                max_batch_per_client=1, policy=policy)
            for r in reqs:
                eng.submit(r)
            outs[policy] = {r.client_id: r.generated for r in eng.run()}
        for c in range(3):
            np.testing.assert_array_equal(outs["opportunistic"][c],
                                          outs["nolockstep"][c])

    def test_stats_count_tokens_not_clients(self, system, lora_cfg):
        """Regression: decode_tokens must count generated tokens (slots
        advanced), not ready clients."""
        cfg, scfg, base, bank = system
        eng = ServingEngine(cfg, lora_cfg, scfg, base, bank,
                            max_batch_per_client=2)
        rng = np.random.default_rng(0)
        n_new = 5
        eng.submit(Request(0, rng.integers(0, cfg.vocab, (2, 8)).astype(np.int32),
                           max_new_tokens=n_new))
        done = eng.run()
        # 2 rows x (n_new - 1) decode steps (first token comes from prefill)
        assert eng.stats["decode_tokens"] == 2 * (n_new - 1)
        assert eng.stats["prefill_tokens"] == 2 * 8
        assert done[0].generated.shape == (2, n_new)

    def test_router_admission_backpressure(self, system, lora_cfg):
        """With a router whose fleet fits one session at a time, requests
        queue until capacity is released, then all complete. The dense
        engine charges a full max_seq-deep slot row (what the dense layout
        physically pins), not the request's context."""
        cfg, scfg, base, bank = system
        need = kvcache.cache_bytes(cfg, scfg.max_seq, 1)
        router = PlacementRouter(cfg, [Slot(0, free_hbm=need * 1.5)],
                                 chip=V5E, host_free_bytes=0)
        eng = ServingEngine(cfg, lora_cfg, scfg, base, bank,
                            max_batch_per_client=1, router=router)
        rng = np.random.default_rng(5)
        for c in range(3):
            eng.submit(Request(c, rng.integers(0, cfg.vocab, (1, 6)).astype(np.int32),
                               max_new_tokens=4))
        done = eng.run()
        assert len(done) == 3
        # serialized by capacity: never more than one client batched per tick
        assert eng.stats["batched_clients"] <= eng.stats["ticks"]
        assert router.slots[0].free_hbm == pytest.approx(need * 1.5)

    def test_recurrent_family_exact_through_slot_reuse(self, key, lora_cfg):
        """Hybrid (Mamba+attention): admission zeroes a slot's recurrent
        state before prefill, so a previous occupant never leaks into the
        next sequence — outputs stay byte-exact through slot turnover."""
        from repro.config import HYBRID
        cfg = tiny(HYBRID)
        scfg = ServeConfig(n_clients=2, max_seq=32)
        base, bank, _ = symbiosis.init_system(cfg, lora_cfg, 2, key)
        rng = np.random.default_rng(0)
        reqs = [Request(0, rng.integers(0, cfg.vocab, (1, 5)).astype(np.int32),
                        max_new_tokens=4),
                Request(1, rng.integers(0, cfg.vocab, (1, 6)).astype(np.int32),
                        max_new_tokens=6, arrive_tick=1),
                Request(0, rng.integers(0, cfg.vocab, (1, 5)).astype(np.int32),
                        max_new_tokens=3, arrive_tick=2)]
        eng = ServingEngine(cfg, lora_cfg, scfg, base, bank,
                            max_batch_per_client=1)   # forces slot reuse
        for r in reqs:
            eng.submit(r)
        done = eng.run()
        assert len(done) == 3
        for r in done:
            ref = _solo_reference(cfg, scfg, base, bank, lora_cfg, r, 1)
            np.testing.assert_array_equal(r.generated, ref)

    def test_bankwide_prefill_ablation_matches(self, system, lora_cfg):
        """The seed-style bank-wide prefill path produces the same outputs
        (it only wastes compute) — used by the benchmark comparison."""
        cfg, scfg, base, bank = system
        rng = np.random.default_rng(9)
        prompts = {c: rng.integers(0, cfg.vocab, (1, 6)).astype(np.int32)
                   for c in range(3)}
        outs = {}
        for mode in (False, True):
            eng = ServingEngine(cfg, lora_cfg, scfg, base, bank,
                                max_batch_per_client=1, bank_prefill=mode)
            for c in range(3):
                eng.submit(Request(c, prompts[c].copy(), max_new_tokens=5))
            outs[mode] = {r.client_id: r.generated for r in eng.run()}
        for c in range(3):
            np.testing.assert_array_equal(outs[False][c], outs[True][c])


class TestPagedServing:
    """ISSUE 2 tentpole: paged + quantized KV slots in the engine."""

    def _run(self, cfg, scfg, base, bank, lora_cfg, reqs, *, max_b=2, **kw):
        eng = ServingEngine(cfg, lora_cfg, scfg, base, bank,
                            max_batch_per_client=max_b, **kw)
        for r in reqs:
            eng.submit(r)
        return eng, eng.run()

    def _workload(self, cfg, rng, n=6):
        return [Request(client_id=i % 3,
                        prompt=rng.integers(0, cfg.vocab,
                                            (1, 4 + 2 * (i % 3))).astype(np.int32),
                        max_new_tokens=(3, 9)[i % 2], arrive_tick=2 * i)
                for i in range(n)]

    def test_paged_engine_matches_dense(self, system, lora_cfg):
        """Fast tier-1 guard: one policy, paged == dense byte-identically."""
        self._policy_case(system, lora_cfg, "opportunistic")

    @pytest.mark.tier2
    @pytest.mark.parametrize("policy", ["lockstep", "nolockstep"])
    def test_paged_engine_matches_dense_policies(self, system, lora_cfg, policy):
        """Paged outputs are byte-identical to the dense engine under every
        tick policy (the acceptance bar of ISSUE 2)."""
        self._policy_case(system, lora_cfg, policy)

    def _policy_case(self, system, lora_cfg, policy):
        cfg, scfg, base, bank = system
        scfg_paged = dataclasses.replace(scfg, page_block=16)
        outs = {}
        for name, sc in (("dense", scfg), ("paged", scfg_paged)):
            rng = np.random.default_rng(7)
            _, done = self._run(cfg, sc, base, bank, lora_cfg,
                                self._workload(cfg, rng), policy=policy)
            outs[name] = sorted((r.client_id, r.prompt.tobytes(),
                                 r.generated.tobytes()) for r in done)
        assert outs["dense"] == outs["paged"]

    def test_page_reuse_no_cross_request_leakage(self, system, lora_cfg):
        """A finishing sequence's pages return to the pool and are re-used
        by the next admit; every occupant still matches solo serving, and
        the allocator drains clean (all pages free, no reservations)."""
        cfg, scfg, base, bank = system
        # pool of 6 8-token pages per client: each request needs 2-3 pages,
        # so 5 sequential client-0 requests MUST recycle pages
        scfg_paged = dataclasses.replace(scfg, page_block=8, pool_pages=6)
        rng = np.random.default_rng(3)
        reqs = [Request(client_id=0,
                        prompt=rng.integers(0, cfg.vocab, (1, 4 + i)).astype(np.int32),
                        max_new_tokens=2 + i)
                for i in range(5)]
        reqs.append(Request(client_id=1,
                            prompt=rng.integers(0, cfg.vocab, (2, 6)).astype(np.int32),
                            max_new_tokens=16))
        eng, done = self._run(cfg, scfg_paged, base, bank, lora_cfg, reqs)
        assert len(done) == 6
        assert all(len(f) == 6 for f in eng._free_pages)
        assert eng._reserved == [0, 0, 0]
        for r in done:
            ref = _solo_reference(cfg, scfg, base, bank, lora_cfg, r, 2)
            np.testing.assert_array_equal(r.generated, ref)

    def test_pool_exhaustion_backpressures_admission(self, system, lora_cfg):
        """Two concurrent client-0 requests need 4 pages; a 3-page pool
        serializes them (admission waits for pages, not only for slots)."""
        cfg, scfg, base, bank = system
        scfg_paged = dataclasses.replace(scfg, page_block=8, pool_pages=3)
        rng = np.random.default_rng(5)
        reqs = [Request(0, rng.integers(0, cfg.vocab, (1, 6)).astype(np.int32),
                        max_new_tokens=4) for _ in range(2)]
        eng, done = self._run(cfg, scfg_paged, base, bank, lora_cfg, reqs)
        assert len(done) == 2
        assert eng.stats["peak_inflight"] == 1     # never concurrent
        for r in done:
            ref = _solo_reference(cfg, scfg, base, bank, lora_cfg, r, 2)
            np.testing.assert_array_equal(r.generated, ref)

    def test_paged_router_charges_pages_not_max_seq(self, system, lora_cfg):
        """At a fixed HBM budget that fits ONE dense max_seq row, the paged
        engine admits several short requests concurrently — the ISSUE 2
        admission claim at test scale."""
        cfg, scfg, base, bank = system
        budget = kvcache.cache_bytes(cfg, scfg.max_seq, 1) * 1.5
        rng = np.random.default_rng(5)
        reqs = lambda: [Request(c, rng.integers(0, cfg.vocab, (1, 6)).astype(np.int32),
                                max_new_tokens=4) for c in range(3)]
        eng_d, done_d = self._run(
            cfg, scfg, base, bank, lora_cfg, reqs(), max_b=1,
            router=PlacementRouter(cfg, [Slot(0, free_hbm=budget)],
                                   chip=V5E, host_free_bytes=0))
        scfg_paged = dataclasses.replace(scfg, page_block=16)
        eng_p, done_p = self._run(
            cfg, scfg_paged, base, bank, lora_cfg, reqs(), max_b=1,
            router=PlacementRouter(cfg, [Slot(0, free_hbm=budget)],
                                   chip=V5E, host_free_bytes=0))
        assert len(done_d) == len(done_p) == 3
        assert eng_d.stats["peak_inflight"] == 1   # dense: serialized by HBM
        assert eng_p.stats["peak_inflight"] == 3   # paged: all fit at once

    def test_quant_prefill_bucketed_matches_dense_tolerance(self, system, lora_cfg):
        """Regression (ISSUE 2 satellite): the engine buckets prefill
        lengths (6 -> 8 here) and prefills into int8-quantized slots; the
        quantized stream must track the dense one within int8 tolerance.
        Compares the post-prefill decode distributions step by step."""
        cfg, scfg, base, bank = system
        scfg_q = dataclasses.replace(scfg, kv_quant=True)
        rng = np.random.default_rng(11)
        prompt = rng.integers(0, cfg.vocab, (1, 6)).astype(np.int32)  # buckets to 8
        logits = {}
        for name, sc in (("dense", scfg), ("quant", scfg_q)):
            eng = ServingEngine(cfg, lora_cfg, sc, base, bank,
                                max_batch_per_client=2)
            assert eng._bucket(6) == 8             # the bucketed path is hit
            eng.submit(Request(0, prompt.copy(), max_new_tokens=1))
            (done,) = eng.run()
            # prefill logits are layout-independent -> compare the argmax
            # token, then step the masked decode once on the filled caches
            active = np.zeros((3, 2), bool)
            active[0, 0] = True
            lg, _ = eng._decode(eng.base, eng.bank, eng.caches,
                                jnp.asarray(eng._last_tok), jnp.asarray(active))
            logits[name] = (done.generated.copy(), np.asarray(lg)[0, 0])
        np.testing.assert_array_equal(logits["dense"][0], logits["quant"][0])
        p_d = jax.nn.softmax(logits["dense"][1])
        p_q = jax.nn.softmax(logits["quant"][1])
        assert float(jnp.abs(p_d - p_q).max()) < 0.02

    def test_paged_quant_engine_serves(self, system, lora_cfg):
        """Paged + int8 compose in the live engine (the bench_multiclient
        admission configuration) and the allocator drains clean."""
        cfg, scfg, base, bank = system
        scfg_pq = dataclasses.replace(scfg, page_block=16, kv_quant=True)
        rng = np.random.default_rng(2)
        eng, done = self._run(cfg, scfg_pq, base, bank, lora_cfg,
                              self._workload(cfg, rng))
        assert len(done) == 6
        assert all(r.generated.shape[1] in (3, 9) for r in done)
        assert eng._reserved == [0, 0, 0]


class TestCacheSpec:
    def test_kv_bytes_formula(self):
        cfg = tiny(DENSE, dtype="bfloat16")
        spec = kvcache.make_cache_spec(cfg)
        expect = cfg.n_layers * cfg.n_kv_heads * cfg.hd * 2 * 2
        assert spec.bytes_per_token == expect
        assert spec.total_bytes(100, 2) == expect * 200

    def test_rwkv_constant_in_seq(self):
        from repro.config import RWKV
        cfg = tiny(RWKV)
        spec = kvcache.make_cache_spec(cfg)
        assert spec.bytes_per_token == 0
        assert spec.total_bytes(1_000_000, 1) == spec.total_bytes(10, 1)

    def test_placement_crossover(self):
        """Fig 19's shape: hetero beats gpu_offload beyond some context."""
        from repro.configs import get_config
        cfg = get_config("symbiosis-llama2-13b")
        short = kvcache.decode_token_cost(cfg, 2_000, placement="gpu", chip=V5E)
        short_h = kvcache.decode_token_cost(cfg, 2_000, placement="hetero", chip=V5E)
        long = kvcache.decode_token_cost(cfg, 131_072, placement="gpu_offload", chip=V5E)
        long_g = kvcache.decode_token_cost(cfg, 131_072, placement="gpu", chip=V5E)
        long_h = kvcache.decode_token_cost(cfg, 131_072, placement="hetero", chip=V5E)
        assert short.total < short_h.total, "all-GPU wins short contexts"
        assert long_g.total == float("inf"), "all-GPU OOMs at 131k (Fig 19)"
        assert long_h.total < long.total, "hetero must win long contexts"


class TestRaggedPrefill:
    """Ragged shared prefill (ISSUE 4 satellite): several same-client
    admissions in one tick share ONE masked prefill call with per-row
    lengths, byte-identical to sequential per-request admission."""

    def _workload(self, cfg, rng):
        # client 0: three different-length prompts due the same tick
        # (ragged rows); client 1: two equal-length prompts; a straggler
        # arrives later and prefills alone
        reqs = [Request(0, rng.integers(0, cfg.vocab, (1, L)).astype(np.int32),
                        max_new_tokens=6) for L in (5, 9, 3)]
        reqs += [Request(1, rng.integers(0, cfg.vocab, (1, 7)).astype(np.int32),
                         max_new_tokens=5) for _ in range(2)]
        reqs.append(Request(1, rng.integers(0, cfg.vocab, (1, 4)).astype(np.int32),
                            max_new_tokens=4, arrive_tick=3))
        return reqs

    @pytest.mark.parametrize("page_block", [0, 16])
    def test_ragged_matches_sequential(self, system, lora_cfg, page_block):
        cfg, scfg, base, bank = system
        sc = dataclasses.replace(scfg, page_block=page_block)
        outs, engines = {}, {}
        for name, ragged in (("ragged", True), ("sequential", False)):
            rng = np.random.default_rng(3)
            eng = ServingEngine(cfg, lora_cfg, sc, base, bank,
                                max_batch_per_client=3, ragged_prefill=ragged)
            for r in self._workload(cfg, rng):
                eng.submit(r)
            done = eng.run()
            outs[name] = sorted((r.client_id, r.prompt.tobytes(),
                                 r.generated.tobytes()) for r in done)
            engines[name] = eng
        assert outs["ragged"] == outs["sequential"]
        if page_block:
            # paged engines route batching through the CROSS-CLIENT compacted
            # prefill (ISSUE 10): the 3+2 same-tick admissions collapse into
            # ONE dispatch (+1 for the straggler)
            assert engines["ragged"].stats["compact_prefill_batches"] == 2
            assert engines["ragged"].stats["prefill_calls"] == 2
        else:
            # dense layout keeps the same-client masked ragged batch:
            # 2 ragged calls (+1 solo for the straggler)
            assert engines["ragged"].stats["ragged_prefill_batches"] == 2
            assert engines["ragged"].stats["prefill_calls"] == 3
        assert engines["sequential"].stats["prefill_calls"] == 6
        assert (engines["ragged"].stats["prefill_tokens"]
                == engines["sequential"].stats["prefill_tokens"])

    def test_ragged_rows_match_solo_serving(self, system, lora_cfg):
        """Each request in a shared ragged prefill still matches serving it
        alone — per-row lengths keep rows independent."""
        cfg, scfg, base, bank = system
        rng = np.random.default_rng(9)
        reqs = [Request(0, rng.integers(0, cfg.vocab, (1, L)).astype(np.int32),
                        max_new_tokens=5) for L in (4, 8)]
        eng = ServingEngine(cfg, lora_cfg, scfg, base, bank,
                            max_batch_per_client=2)
        for r in reqs:
            eng.submit(r)
        done = eng.run()
        assert eng.stats["ragged_prefill_batches"] == 1
        for r in done:
            ref = _solo_reference(cfg, scfg, base, bank, lora_cfg, r, 2)
            np.testing.assert_array_equal(r.generated, ref)

    def test_recurrent_families_reject_ragged(self, key, lora_cfg):
        """Right-padding rows to a shared bucket would pollute recurrent
        state: hybrid/RWKV engines refuse the knob (and default it off)."""
        from repro.config import HYBRID
        cfg = tiny(HYBRID)
        scfg = ServeConfig(n_clients=2, max_seq=48)
        base, bank, _ = symbiosis.init_system(cfg, lora_cfg, 2, key)
        with pytest.raises(ValueError, match="attention families"):
            ServingEngine(cfg, lora_cfg, scfg, base, bank,
                          ragged_prefill=True)
        eng = ServingEngine(cfg, lora_cfg, scfg, base, bank)
        assert not eng._ragged

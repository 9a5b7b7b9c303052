"""Fine-tuning-as-a-service: FinetuneEngine / SymbiosisEngine behaviour.

The load-bearing contract (ISSUE 4): every job admitted to the service —
whatever its PEFT method, hyperparameters, or the join/leave churn and
decode interleaving around it — produces per-step grads, adapter params and
optimizer state BITWISE equal to a dedicated ``make_baseline_train_step``
run of that job alone."""
import functools

import jax
import numpy as np
import pytest

from repro.common.hardware import V5E
from repro.config import AdapterConfig, FinetuneConfig, ServeConfig, TrainConfig
from repro.core import adapters as ad_lib
from repro.core import symbiosis
from repro.models import get_model
from repro.optim import adamw_init
from repro.serving.engine import Request, ServingEngine
from repro.serving.router import PlacementRouter, Slot
from repro.training import (FinetuneEngine, FinetuneJob, SymbiosisEngine,
                            job_hbm_bytes, make_job_stream)
from conftest import tiny


def _job(cfg, method="lora", seed=0, steps=4, batch=2, seq=16, **kw):
    targets = {"lora": ("q", "v"), "ia3": ("k", "v", "down"),
               "prefix": ("q", "v")}[method]
    acfg = kw.pop("acfg", None) or AdapterConfig(method=method, rank=4,
                                                 alpha=8.0, targets=targets)
    defaults = {"lr": 1e-2, "warmup_steps": 1, "max_grad_norm": 1.0}
    defaults.update(kw)
    return FinetuneJob(acfg=acfg, data=make_job_stream(cfg, batch, seq, seed=seed),
                       batch_size=batch, seq_len=seq, steps=steps, seed=seed,
                       name=f"{method}-{seed}", **defaults)


@functools.lru_cache(maxsize=None)
def _oracle_step(cfg, acfg, tcfg):
    """One oracle compile per config tuple across the whole module."""
    return jax.jit(symbiosis.make_baseline_train_step(cfg, acfg, tcfg))


def _solo_oracle(cfg, base, job):
    """The dedicated run: make_baseline_train_step (its DEFAULT form — the
    torch-like baseline that differentiates through the base) over the
    job's own stream/schedule. Returns (adapter, opt, losses, gnorms)."""
    tcfg = TrainConfig(lr=job.lr, weight_decay=job.weight_decay,
                       warmup_steps=job.warmup_steps,
                       total_steps=job.schedule_total,
                       max_grad_norm=job.max_grad_norm, remat=False,
                       microbatch=job.microbatch)
    step_fn = _oracle_step(cfg, job.acfg, tcfg)
    adapter = ad_lib.init_adapter(cfg, job.acfg, jax.random.PRNGKey(job.seed))
    opt = adamw_init(adapter)
    losses, gnorms = [], []
    for t in range(job.start_step, job.steps):
        adapter, opt, m = step_fn(base, adapter, opt, job.data.batch(t), t)
        losses.append(float(np.asarray(m["loss"])))
        gnorms.append(np.asarray(m["gnorm"]))
    return adapter, opt, losses, gnorms


def _assert_job_matches_oracle(cfg, base, job):
    # Comparing the FULL optimizer state bitwise pins the PER-STEP grads,
    # not just the endpoint: m_1 = (1-b1)·g_1 exactly, and each m_t/v_t is
    # reconstructible from (m_{t-1}, g_t) — so any step's grad deviating by
    # even one bit would surface in the final moments.
    adapter, opt, losses, _ = _solo_oracle(cfg, base, job)
    assert job.result is not None, f"{job.name} never retired"
    for a, b in zip(jax.tree.leaves((adapter, opt)),
                    jax.tree.leaves((job.result.adapter, job.result.opt))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{job.name} diverged from solo")
    # the loss scalar is a reduction over the same logits; XLA may fuse it
    # differently per row-bucket shape (grads/params above are the bitwise
    # contract), so last-bits tolerance here
    np.testing.assert_allclose(job.result.losses, losses, rtol=1e-6)


@pytest.fixture
def base(key):
    return get_model(tiny()).init_params(key)


def _solo_reference_via_engine(cfg, scfg, base, bank, acfg, req):
    """The request served alone through a fresh, router-less engine."""
    eng = ServingEngine(cfg, acfg, scfg, base, bank, max_batch_per_client=1)
    solo = Request(client_id=req.client_id, prompt=req.prompt.copy(),
                   max_new_tokens=req.max_new_tokens)
    eng.submit(solo)
    (done,) = eng.run()
    return done.generated


class TestByteIdentity:
    @pytest.mark.parametrize("method", ["lora", "ia3", "prefix"])
    def test_bank_matches_solo_baseline(self, base, method):
        """One bank, three jobs with HETEROGENEOUS hyperparameters (lr,
        weight decay, clipping, schedules): each matches its dedicated
        run bitwise."""
        cfg = tiny()
        eng = FinetuneEngine(cfg, base)
        jobs = [
            _job(cfg, method, seed=0, steps=3, lr=1e-2, weight_decay=0.0),
            _job(cfg, method, seed=1, steps=4, lr=3e-3, weight_decay=0.1,
                 max_grad_norm=0.5, warmup_steps=0, total_steps=20),
            _job(cfg, method, seed=2, steps=5, lr=1e-3, max_grad_norm=0.0),
        ]
        for j in jobs:
            eng.submit(j)
        done = eng.run()
        assert len(done) == 3
        assert len(eng._banks) == 1, "same AdapterConfig+shape must share a bank"
        for j in jobs:
            _assert_job_matches_oracle(cfg, base, j)

    def test_join_leave_churn_byte_identity(self, base):
        """Jobs joining mid-run and leaving early never change any job's
        math — admission/retirement only decides WHICH rows exist."""
        cfg = tiny()
        eng = FinetuneEngine(cfg, base)
        j0 = _job(cfg, "lora", seed=0, steps=6)
        j1 = _job(cfg, "lora", seed=1, steps=2)       # leaves early
        eng.submit(j0)
        eng.submit(j1)
        for _ in range(2):
            eng.train_tick()
        j2 = _job(cfg, "lora", seed=2, steps=3)       # joins mid-run
        eng.submit(j2)
        eng.run()
        for j in (j0, j1, j2):
            _assert_job_matches_oracle(cfg, base, j)

    def test_explicit_mid_run_retire(self, base):
        """An explicitly retired job hands back exactly the state of the
        steps it ran; survivors complete unperturbed."""
        cfg = tiny()
        eng = FinetuneEngine(cfg, base)
        j0 = _job(cfg, "lora", seed=0, steps=8)
        j1 = _job(cfg, "lora", seed=1, steps=4)
        eng.submit(j0)
        eng.submit(j1)
        for _ in range(3):
            eng.train_tick()
        res = eng.retire(j0)                           # leaves at step 3
        assert res.step == 3
        eng.run()
        # oracle over the 3 steps actually run, on the ORIGINAL schedule
        # horizon (retiring early doesn't rewrite the lr schedule)
        j0.total_steps = j0.schedule_total
        j0.steps = 3
        _assert_job_matches_oracle(cfg, base, j0)
        _assert_job_matches_oracle(cfg, base, j1)

    def test_heterogeneous_banks_one_engine(self, base):
        """LoRA + IA3 + prefix + a different rank + a different batch shape:
        five jobs, several banks, ONE engine, one base — and every job
        still bitwise-matches its dedicated run (the multi-bank
        heterogeneous-methods ROADMAP item)."""
        cfg = tiny()
        eng = FinetuneEngine(cfg, base)
        jobs = [
            _job(cfg, "lora", seed=0, steps=3),
            _job(cfg, "lora", seed=1, steps=3,
                 acfg=AdapterConfig(method="lora", rank=8, alpha=16.0,
                                    targets=("q", "k", "v", "o"))),
            _job(cfg, "ia3", seed=2, steps=4),
            _job(cfg, "prefix", seed=3, steps=4),
            _job(cfg, "lora", seed=4, steps=3, batch=4),   # same acfg, new shape
        ]
        for j in jobs:
            eng.submit(j)
        eng.run()
        assert len(eng._banks) == 5
        for j in jobs:
            _assert_job_matches_oracle(cfg, base, j)

    def test_bank_capacity_growth(self, base):
        """More jobs than any initial bucket: the bank doubles its capacity
        under admission without disturbing already-resident jobs."""
        cfg = tiny()
        eng = FinetuneEngine(cfg, base)
        jobs = [_job(cfg, "lora", seed=i, steps=2 + i % 2) for i in range(5)]
        for j in jobs:
            eng.submit(j)
        eng.run()
        (bank,) = eng._banks.values()
        assert bank.cap == 8
        for j in jobs:
            _assert_job_matches_oracle(cfg, base, j)

    def test_microbatched_job_matches_solo(self, base):
        """Grad-accum microbatching is part of the bank key and of the
        shared row-grads program — accumulation math identical to solo."""
        cfg = tiny()
        eng = FinetuneEngine(cfg, base)
        jobs = [_job(cfg, "lora", seed=0, steps=3, batch=4, microbatch=2),
                _job(cfg, "lora", seed=1, steps=3, batch=4)]   # separate bank
        for j in jobs:
            eng.submit(j)
        eng.run()
        assert len(eng._banks) == 2
        for j in jobs:
            _assert_job_matches_oracle(cfg, base, j)


class TestIsolation:
    def test_churn_never_perturbs_survivors(self, base):
        """Satellite: the training analogue of the serving cross-client
        isolation test. A survivor's per-tick params, optimizer state and
        loss sequence are identical whether or not other jobs join/leave
        around it — snapshots compared tick by tick, bitwise."""
        cfg = tiny()

        def survivor():
            return _job(cfg, "lora", seed=0, steps=5)

        def run(churn):
            eng = FinetuneEngine(cfg, base)
            job = survivor()
            eng.submit(job)
            if churn:
                eng.submit(_job(cfg, "lora", seed=1, steps=2))
            snaps = []
            t = 0
            while eng.pending():
                if churn and t == 2:
                    eng.submit(_job(cfg, "lora", seed=2, steps=2))
                eng.train_tick()
                if job.result is None:
                    snaps.append(jax.tree.map(np.asarray,
                                              eng.job_state(job)[:2]))
                t += 1
            return job, snaps

        quiet_job, quiet_snaps = run(churn=False)
        churn_job, churn_snaps = run(churn=True)
        # params/opt below are the bitwise contract; the loss SCALAR is a
        # report whose final reduction XLA fuses differently per row-bucket
        # shape (churn changes the bucket), hence last-bits tolerance
        np.testing.assert_allclose(quiet_job.result.losses,
                                   churn_job.result.losses, rtol=1e-6)
        for sq, sc in zip(quiet_snaps, churn_snaps):
            for a, b in zip(jax.tree.leaves(sq), jax.tree.leaves(sc)):
                np.testing.assert_array_equal(a, b)
        for a, b in zip(
                jax.tree.leaves((quiet_job.result.adapter, quiet_job.result.opt)),
                jax.tree.leaves((churn_job.result.adapter, churn_job.result.opt))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestCheckpointResume:
    def test_retire_checkpoint_readmit_bitwise(self, base, tmp_path):
        """Satellite: a job retired mid-service, checkpointed, and
        re-admitted resumes with bitwise-identical adapter + optimizer
        state and continues the SAME loss trajectory as the uninterrupted
        run."""
        from repro.checkpoint import restore_job_state, save_job_state
        cfg = tiny()
        # uninterrupted reference (through the engine, alongside a neighbour
        # so bucket shapes match the interrupted run's early ticks)
        ref_eng = FinetuneEngine(cfg, base)
        ref = _job(cfg, "ia3", seed=0, steps=6)
        ref_eng.submit(ref)
        ref_eng.submit(_job(cfg, "ia3", seed=1, steps=3))
        ref_eng.run()

        eng = FinetuneEngine(cfg, base)
        job = _job(cfg, "ia3", seed=0, steps=6)
        eng.submit(job)
        eng.submit(_job(cfg, "ia3", seed=1, steps=3))
        for _ in range(3):
            eng.train_tick()
        res = eng.retire(job)
        assert res.step == 3
        save_job_state(tmp_path, res.step, res.adapter, res.opt, name="j")
        like_a = ad_lib.init_adapter(cfg, job.acfg, jax.random.PRNGKey(9))
        adapter, opt = restore_job_state(tmp_path, res.step, like_a,
                                         adamw_init(like_a), name="j")
        # roundtrip is exact
        for a, b in zip(jax.tree.leaves((res.adapter, res.opt)),
                        jax.tree.leaves((adapter, opt))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        resumed = _job(cfg, "ia3", seed=0, steps=6)
        resumed.init_adapter, resumed.init_opt = adapter, opt
        resumed.start_step = res.step
        eng.submit(resumed)
        eng.run()
        assert resumed.result.step == 6
        for a, b in zip(jax.tree.leaves((ref.result.adapter, ref.result.opt)),
                        jax.tree.leaves((resumed.result.adapter,
                                         resumed.result.opt))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(res.losses + resumed.result.losses,
                                   ref.result.losses, rtol=1e-6)
        # and the oracle agrees end to end
        _assert_job_matches_oracle(cfg, base, ref)


class TestAdmission:
    def test_router_backpressure_serializes_jobs(self, base):
        """With one slot sized for a single job's adapter+optimizer+
        activation charge, a second job queues until the first retires."""
        cfg = tiny()
        probe = _job(cfg, "lora", seed=0, steps=2)
        nbytes = job_hbm_bytes(cfg, probe)
        router = PlacementRouter(cfg, [Slot(0, free_hbm=nbytes * 1.5)],
                                 chip=V5E, host_free_bytes=0)
        eng = FinetuneEngine(cfg, base, router=router)
        eng.submit(_job(cfg, "lora", seed=0, steps=2))
        eng.submit(_job(cfg, "lora", seed=1, steps=2))
        done = eng.run()
        assert len(done) == 2
        assert eng.stats["peak_jobs"] == 1
        for j in done:
            _assert_job_matches_oracle(cfg, base, j)

    def test_unadmittable_job_raises(self, base):
        cfg = tiny()
        router = PlacementRouter(cfg, [Slot(0, free_hbm=16.0)],
                                 chip=V5E, host_free_bytes=0)
        eng = FinetuneEngine(cfg, base, router=router)
        eng.submit(_job(cfg, "lora", seed=0, steps=2))
        with pytest.raises(RuntimeError, match="never be admitted"):
            eng.run()

    def test_max_jobs_ceiling(self, base):
        cfg = tiny()
        eng = FinetuneEngine(cfg, base, fcfg=FinetuneConfig(max_jobs=2))
        for i in range(4):
            eng.submit(_job(cfg, "lora", seed=i, steps=2))
        done = eng.run()
        assert len(done) == 4 and eng.stats["peak_jobs"] == 2

    def test_submit_validation(self, base):
        cfg = tiny()
        eng = FinetuneEngine(cfg, base)
        bad = _job(cfg, "lora", steps=2)
        bad.init_adapter = {}
        with pytest.raises(ValueError, match="both init_adapter and init_opt"):
            eng.submit(bad)
        late = _job(cfg, "lora", steps=2)
        late.start_step = 2
        with pytest.raises(ValueError, match="nothing to run"):
            eng.submit(late)


class TestSymbiosisService:
    def _system(self, key):
        cfg = tiny()
        acfg = AdapterConfig(method="lora", rank=4, alpha=8.0, targets=("q", "v"))
        scfg = ServeConfig(n_clients=2, max_seq=48)
        base, bank, _ = symbiosis.init_system(cfg, acfg, 2, key)
        return cfg, acfg, scfg, base, bank

    def _requests(self, cfg):
        rng = np.random.default_rng(5)
        return [Request(client_id=i % 2,
                        prompt=rng.integers(0, cfg.vocab, (1, 6)).astype(np.int32),
                        max_new_tokens=7, arrive_tick=i) for i in range(4)]

    def test_interleaving_changes_nothing(self, key):
        """Decode ticks interleaved with train steps against ONE base:
        serving outputs and every job's trajectory are identical to each
        engine running alone."""
        cfg, acfg, scfg, base, bank = self._system(key)

        def jobs():
            return [_job(cfg, "lora", seed=0, steps=4),
                    _job(cfg, "ia3", seed=1, steps=6)]

        sym = SymbiosisEngine(
            serving=ServingEngine(cfg, acfg, scfg, base, bank,
                                  max_batch_per_client=2),
            finetune=FinetuneEngine(cfg, base))
        mixed_reqs, mixed_jobs = self._requests(cfg), jobs()
        for r in mixed_reqs:
            sym.submit(r)
        for j in mixed_jobs:
            sym.submit(j)
        done_r, done_j = sym.run()
        assert len(done_r) == 4 and len(done_j) == 2
        assert sym.stats["decode_ticks"] > 0 and sym.stats["train_ticks"] > 0

        solo_serv = ServingEngine(cfg, acfg, scfg, base, bank,
                                  max_batch_per_client=2)
        solo_reqs = self._requests(cfg)
        for r in solo_reqs:
            solo_serv.submit(r)
        solo_serv.run()
        for a, b in zip(mixed_reqs, solo_reqs):
            np.testing.assert_array_equal(a.generated, b.generated)

        solo_ft = FinetuneEngine(cfg, base)
        solo_jobs = jobs()
        for j in solo_jobs:
            solo_ft.submit(j)
        solo_ft.run()
        for a, b in zip(mixed_jobs, solo_jobs):
            assert a.result.losses == b.result.losses
            for x, y in zip(jax.tree.leaves((a.result.adapter, a.result.opt)),
                            jax.tree.leaves((b.result.adapter, b.result.opt))):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            _assert_job_matches_oracle(cfg, base, a)

    def test_shared_router_stall_is_not_fatal(self, key):
        """ONE PlacementRouter shared by both engines: a request queued
        behind HBM pinned by a fine-tuning job must WAIT (not trip the
        standalone 'can never be admitted' error) and admit once the job
        retires — and vice versa. Standalone engines still raise."""
        cfg, acfg, scfg, base, bank = self._system(key)
        from repro.serving import kvcache
        job = _job(cfg, "lora", seed=0, steps=3)
        req_need = kvcache.cache_bytes(cfg, scfg.max_seq, 1)
        job_need = job_hbm_bytes(cfg, job)
        # fits the training job OR one request, never both
        router = PlacementRouter(
            cfg, [Slot(0, free_hbm=max(req_need, job_need) * 1.2)],
            chip=V5E, host_free_bytes=0)
        serving = ServingEngine(cfg, acfg, scfg, base, bank,
                                max_batch_per_client=1, router=router)
        ft = FinetuneEngine(cfg, base, router=router)
        sym = SymbiosisEngine(serving=serving, finetune=ft)
        sym.submit(job)
        sym.tick()                        # job admitted, holds the slot HBM
        req = self._requests(cfg)[0]
        req.arrive_tick = 0
        sym.submit(req)
        done_r, done_j = sym.run()        # must NOT raise
        assert len(done_r) == 1 and len(done_j) == 1
        assert sym.stats["admission_stalls"] > 0
        np.testing.assert_array_equal(
            done_r[0].generated,
            _solo_reference_via_engine(cfg, scfg, base, bank, acfg, req))
        # the standalone engine still fails fast when truly stuck
        solo = ServingEngine(cfg, acfg, scfg, base, bank,
                             max_batch_per_client=1,
                             router=PlacementRouter(cfg, [Slot(0, free_hbm=16.0)],
                                                    chip=V5E, host_free_bytes=0))
        solo.submit(self._requests(cfg)[0])
        with pytest.raises(RuntimeError, match="never be admitted"):
            solo.run()

    def test_rejects_split_base(self, key):
        """A COPY of the base is not the shared base — admitting it would
        silently double base HBM, the thing the service exists to avoid."""
        cfg, acfg, scfg, base, bank = self._system(key)
        serving = ServingEngine(cfg, acfg, scfg, base, bank)
        copied = jax.tree.map(lambda x: x + 0, base)
        with pytest.raises(ValueError, match="share ONE frozen base"):
            SymbiosisEngine(serving=serving,
                            finetune=FinetuneEngine(cfg, copied))

    def test_train_only_and_serve_only(self, key):
        cfg, acfg, scfg, base, bank = self._system(key)
        with pytest.raises(ValueError):
            SymbiosisEngine()
        ft = FinetuneEngine(cfg, base)
        sym = SymbiosisEngine(finetune=ft)
        job = _job(cfg, "lora", seed=0, steps=2)
        sym.submit(job)
        done_r, done_j = sym.run()
        assert done_r == [] and len(done_j) == 1
        with pytest.raises(ValueError, match="no serving engine"):
            sym.submit(self._requests(cfg)[0])

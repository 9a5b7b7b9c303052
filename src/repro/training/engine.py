"""FinetuneEngine: fine-tuning as a service over one shared frozen base.

The training-side twin of ``serving.ServingEngine`` (paper §3, §5): tenants
``submit()`` ``FinetuneJob``s — each with its own PEFT method/rank/targets,
AdamW hyperparameters + warmup-cosine schedule, data stream and grad-accum
microbatching — and the engine time-shares ONE resident copy of the frozen
base params across all of them, admitting and retiring jobs mid-run.

Architecture
------------
* **Banks.** Jobs whose step programs can share one vmapped call — same
  ``AdapterConfig``, per-step batch shape and microbatch factor — are
  grouped into a bank: adapter params and AdamW state stacked along a
  leading bank-slot axis. Heterogeneous jobs (LoRA + IA3 + prefix, mixed
  ranks/batch shapes) form SEPARATE banks inside the same engine, all
  closing over the same base tree — the multi-bank heterogeneous-methods
  service the adapter ecosystem needs (LLM-Adapters), without replicating
  the base.
* **Bucketed membership.** A bank's capacity grows by doubling and each
  tick gathers the active slots into a power-of-two row bucket
  (``core.symbiosis.make_compact_train_step``), so join/leave churn causes
  a bounded number of recompiles and a sparse bank pays compute for its
  ACTIVE jobs, not its high-water mark.
* **Byte-identity.** A bank row runs the exact ``make_row_grad_fn``
  program its solo ``make_baseline_train_step`` oracle runs, and the
  scatter back into the bank only touches the gathered rows — so every
  job's per-step grads, adapter params and optimizer state match its
  dedicated run bit-for-bit, and churn around a job can never perturb it.
* **Admission.** Each tick scans the queue in submit order, gated by
  ``FinetuneConfig.max_jobs`` and (when a ``PlacementRouter`` is attached)
  by an HBM charge for what a job actually pins: adapter params + AdamW
  moments + an activation working-set estimate (``job_hbm_bytes``). A job
  that doesn't fit stays queued without blocking later jobs (the serving
  engine's continuous-admission rule); capacity releases at retire, and
  queued jobs take it on the next tick.
* **Retire / resume.** A job retires when its step budget is exhausted or
  on explicit ``retire()``; its ``JobResult`` carries the final adapter +
  optimizer state. Re-submitting that state (``init_adapter`` /
  ``init_opt`` / ``start_step``) continues the same trajectory bitwise —
  the checkpoint/resume story of a service whose clients own their state.

Driven standalone via ``run()``, or interleaved tick-by-tick with a
``ServingEngine`` against the same donated base by
``training.SymbiosisEngine``.

Observability (docs/observability.md): construct with ``obs=Obs()`` and
the engine emits tick-phase spans (admit / compact gather / train step /
device sync / scatter), per-job counters (``train_steps_total``,
``train_tokens_total``, ``train_loss``), and structured events (admit,
retire, backoff, retry, quarantine, compile) drainable via
``drain_events()``. Telemetry is strictly additive: with ``obs=None``
(the default) the hot path takes a no-op span and skips every metric
callback, and with it enabled all timestamps land at tick boundaries —
committed results stay bitwise identical either way.

Machine-checked invariants (docs/invariants.md): frozen-base taint (a
train step must never produce a base-shaped output that isn't a declared
update), donation of bank/optimizer state, per-row isolation, and closed
jit bucket coverage via ``trace_domain()`` +
``repro.analysis.tracecount.dispatch`` are enforced by
``python -m repro.analysis`` and tested in tests/test_analysis.py.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import warnings
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import tracecount
from repro.config import AdapterConfig, FinetuneConfig, ModelConfig
from repro.core import adapters as adapters_lib
from repro.core import symbiosis
from repro.core.engine_spec import EngineSpec
from repro.faults.health import (AdmissionStall, HealthPolicy, HealthRecord,
                                 classify)
from repro.faults.plan import NonFiniteFault, StreamExhausted, TransientFault
from repro.optim import adamw_init
from repro.training.job import FinetuneJob, JobResult

# telemetry-off spans: one shared nullcontext, zero per-phase allocation
# (docs/observability.md — the disabled mode must cost nothing on the tick)
_NULL_CTX = contextlib.nullcontext()


def _null_span(name):
    return _NULL_CTX


def _pin_train(fn, cfg, mesh):
    """Sharded hot path: pin the donated bank/optimizer trees to their
    client-axis specs on the way IN and OUT of the jitted step (the
    training twin of ``serving.engine._pin_serving``) — donated state keeps
    ONE placement across ticks and the row gather/scatter never round-trips
    through a replicated layout. ``mesh=None`` returns ``fn`` untouched."""
    if mesh is None:
        return fn
    from repro.launch import shardings

    def pinned(base, bank, opt, batch, slots, row_mask, hyper):
        bank = shardings.bank_state_constrain(cfg, mesh, bank)
        opt = shardings.bank_state_constrain(cfg, mesh, opt)
        new_bank, new_opt, metrics = fn(base, bank, opt, batch, slots,
                                        row_mask, hyper)
        return (shardings.bank_state_constrain(cfg, mesh, new_bank),
                shardings.bank_state_constrain(cfg, mesh, new_opt), metrics)

    return symbiosis.program_named(pinned, fn.__name__)


# One compile cache per (model, adapter-config, step knobs) shared by every
# engine instance (``mesh`` joins the key — a sharded engine gets its own
# jitted wrapper); bank/opt (args 1, 2) are donated — the engine always
# rebinds them, so XLA updates the stacked job state in place.
@functools.lru_cache(maxsize=None)
def _jit_compact_train(cfg, acfg, microbatch, memory_optimized, remat,
                       mesh=None):
    return jax.jit(_pin_train(symbiosis.make_compact_train_step(
        cfg, acfg, microbatch=microbatch, memory_optimized=memory_optimized,
        remat=remat), cfg, mesh), donate_argnums=(1, 2))


@dataclasses.dataclass(frozen=True)
class BankKey:
    """Jobs sharing one vmapped step program: same PEFT config, same
    per-step batch shape, same grad-accum factor."""
    acfg: AdapterConfig
    batch: int
    seq: int
    microbatch: int


class _Bank:
    """One bank's stacked state. ``slots[i]`` is the occupying job (or
    None); params/opt leaves carry the matching leading [cap] axis.

    ``reserve`` (from ``BankSpec.capacity``) pre-sizes the first
    allocation: the stacked leaves come up at the next power of two >=
    reserve instead of growing 1 -> 2 -> 4 under churn. Row buckets are
    ``min(pow2(active), cap)`` either way, so a reserved bank runs the
    SAME bucketed programs as a doubling-grown one — byte-identity is
    unaffected; only the number of growth reallocations changes."""

    def __init__(self, key: BankKey, reserve: int = 0):
        self.key = key
        self.reserve = reserve
        self.params = None
        self.opt = None
        self.slots: List[Optional[FinetuneJob]] = []

    @property
    def cap(self) -> int:
        return len(self.slots)

    def alloc(self, adapter, opt_state) -> int:
        """Place one job's state into a free slot, growing cap 1 -> 2 -> 4
        ... by zero-padding the stacked leaves when the bank is full."""
        if None not in self.slots:
            if self.params is None:
                cap0 = 1
                while cap0 < self.reserve:
                    cap0 *= 2
                zero = lambda x: jnp.zeros((cap0,) + x.shape, x.dtype)
                self.params = jax.tree.map(zero, adapter)
                self.opt = jax.tree.map(zero, opt_state)
                self.slots = [None] * cap0
            else:
                grow = self.cap                      # double
                pad = lambda x: jnp.concatenate(
                    [x, jnp.zeros((grow,) + x.shape[1:], x.dtype)])
                self.params = jax.tree.map(pad, self.params)
                self.opt = jax.tree.map(pad, self.opt)
                self.slots.extend([None] * grow)
                return self._write(self.slots.index(None), adapter, opt_state)
            return self._write(0, adapter, opt_state)
        return self._write(self.slots.index(None), adapter, opt_state)

    def _write(self, slot, adapter, opt_state) -> int:
        wr = lambda full, one: full.at[slot].set(one.astype(full.dtype))
        self.params = jax.tree.map(wr, self.params, adapter)
        self.opt = jax.tree.map(wr, self.opt, opt_state)
        return slot

    def read(self, slot):
        return (jax.tree.map(lambda x: x[slot], self.params),
                jax.tree.map(lambda x: x[slot], self.opt))


def job_hbm_bytes(cfg: ModelConfig, job: FinetuneJob, *,
                  remat: bool = False) -> int:
    """Admission charge for one job: what fine-tuning actually pins beyond
    the (already-resident, shared) base — adapter params, the two f32 AdamW
    moment trees, and an activation working-set estimate (per-microbatch
    live tokens × residual stream, plus the logits block)."""
    n_params, adapter_b = adapters_lib.adapter_bytes(cfg, job.acfg)
    opt_b = 2 * n_params * 4
    nmb = max(1, job.microbatch)
    if job.batch_size % nmb or job.batch_size == nmb:
        nmb = 1     # make_row_grad_fn falls back to one full-batch grad —
        #             charge the activations the job will actually hold
    tokens = job.batch_size * job.seq_len // nmb
    layers_live = 2 if remat else cfg.n_layers
    act_b = 4 * tokens * (layers_live * cfg.d_model + cfg.vocab)
    return adapter_b + opt_b + act_b


class FinetuneEngine:
    """One frozen base continuously fine-tuned against by a churn of jobs.

    CONSTRUCTION (``core.engine_spec.EngineSpec``)::

        spec = EngineSpec(cfg=cfg, banks=(BankSpec("lora8", lora, 8),),
                          finetune=FinetuneConfig(max_jobs=8), mesh=None)
        engine = FinetuneEngine(spec, base_params)

    Each ``BankSpec`` pre-reserves its capacity for jobs matching its
    AdapterConfig (the stacked state comes up at the declared size instead
    of doubling under churn — same bucketed step programs, fewer
    reallocations). ``spec.mesh`` shards the engine: the frozen base by
    ``launch.shardings.base_param_specs`` (or replicated with
    ``spec.replicate_base``), bank params + optimizer state with their
    bank-slot axis over the batch axes; ``mesh=None`` is byte-identical to
    the single-device engine.

    FAULT CONTAINMENT (docs/robustness.md): per-job health records with
    tick-count backoff, per-row finite probes fused into the compact step
    (poisoned commits dropped in-scatter), quarantine-with-checkpoint,
    transactional admission, ``finished_early`` stream exhaustion, and
    whole-engine ``engine_state()`` / ``load_engine_state()`` crash
    recovery — survivors stay bitwise identical to a never-faulted run.

    DEPRECATED: the positional form ``FinetuneEngine(cfg, base_params,
    fcfg=..., router=...)`` still works but emits a ``DeprecationWarning``
    — migrate to the EngineSpec form (see docs/sharding.md)."""

    def __init__(self, spec, base_params, *,
                 fcfg: Optional[FinetuneConfig] = None, router=None,
                 health_policy: Optional[HealthPolicy] = None,
                 quarantine_dir: Optional[str] = None, debug: bool = False,
                 fault_hook=None, obs=None):
        if isinstance(spec, EngineSpec):
            if fcfg is not None:
                raise TypeError("pass the FinetuneConfig as EngineSpec."
                                "finetune, not fcfg=")
            self._setup(spec.cfg, base_params, fcfg=spec.finetune,
                        router=router, mesh=spec.mesh,
                        replicate_base=spec.replicate_base,
                        reserve={b.acfg: b.capacity for b in spec.banks},
                        spec=spec, health_policy=health_policy,
                        quarantine_dir=quarantine_dir, debug=debug,
                        fault_hook=fault_hook, obs=obs)
        else:
            warnings.warn(
                "FinetuneEngine(cfg, base_params) is deprecated; construct "
                "an EngineSpec and call FinetuneEngine(spec, base_params) "
                "(docs/sharding.md)", DeprecationWarning, stacklevel=2)
            self._setup(spec, base_params, fcfg=fcfg, router=router,
                        health_policy=health_policy,
                        quarantine_dir=quarantine_dir, debug=debug,
                        fault_hook=fault_hook, obs=obs)

    def _setup(self, cfg: ModelConfig, base_params, *,
               fcfg: Optional[FinetuneConfig] = None, router=None,
               mesh=None, replicate_base: bool = False,
               reserve: Optional[Dict[AdapterConfig, int]] = None,
               spec: Optional[EngineSpec] = None,
               health_policy: Optional[HealthPolicy] = None,
               quarantine_dir: Optional[str] = None, debug: bool = False,
               fault_hook=None, obs=None):
        self.cfg = cfg
        self.spec = spec
        self.mesh = mesh
        self._replicate_base = replicate_base
        self._reserve = reserve or {}
        if mesh is not None:
            from repro.launch import shardings
            # idempotent + identity-preserving (see ServingEngine._setup):
            # a base already placed by SymbiosisEngine.from_spec passes
            # through untouched, keeping the shared-base identity check
            base_params = shardings.shard_base_params(
                cfg, mesh, base_params, replicate=replicate_base)
        self.base = base_params
        self.fcfg = fcfg or FinetuneConfig()
        self.router = router
        self._queue: List[FinetuneJob] = []
        self._banks: Dict[BankKey, _Bank] = {}
        self._slot_of: Dict[int, tuple] = {}      # id(job) -> (BankKey, slot)
        self._step_of: Dict[int, int] = {}        # id(job) -> next global step
        self._placement: Dict[int, object] = {}
        self.finished: List[FinetuneJob] = []
        # fault containment (docs/robustness.md): per-job health records
        # live on the jobs themselves; quarantined jobs checkpoint to
        # quarantine_dir (when set) before retiring; debug runs the
        # conservation audit after every tick; fault_hook is the injection
        # point for the chaos sweep (called per admission attempt)
        self.health_policy = health_policy or HealthPolicy()
        self.quarantine_dir = quarantine_dir
        self.debug = debug
        self.fault_hook = fault_hook
        self._admission_faulted = False
        self.stats = {"train_ticks": 0, "train_steps": 0, "admitted": 0,
                      "retired": 0, "peak_jobs": 0, "compact_rows": 0,
                      "compact_padded": 0, "train_tokens": 0,
                      "faults": 0, "quarantined": 0, "finished_early": 0,
                      "dropped_steps": 0}
        # telemetry (docs/observability.md): obs=None keeps the tick loop
        # free of any timing machinery — _span is a shared nullcontext
        self._obs = obs
        self._span = _null_span if obs is None else obs.span
        if obs is not None:
            obs.attach("finetune", self)

    # ------------------------------------------------------------------
    def submit(self, job: FinetuneJob):
        if (job.init_adapter is None) != (job.init_opt is None):
            raise ValueError("resume needs both init_adapter and init_opt")
        if job.start_step >= job.steps:
            raise ValueError(f"start_step {job.start_step} >= step budget "
                             f"{job.steps}: nothing to run")
        nmb = job.microbatch
        if nmb and nmb > 1 and (job.batch_size % nmb or job.batch_size == nmb):
            # make_row_grad_fn would silently fall back to one full-batch
            # grad — the tenant asked for accumulation to SHRINK activation
            # memory, so refuse loudly instead of undercharging admission
            raise ValueError(
                f"microbatch {nmb} must strictly divide batch_size "
                f"{job.batch_size} (a non-dividing or degenerate factor "
                f"runs full-batch and holds full-batch activations)")
        self._queue.append(job)

    def pending(self) -> bool:
        return bool(self._queue or self._slot_of)

    @property
    def n_active(self) -> int:
        return len(self._slot_of)

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def _bank_key(self, job: FinetuneJob) -> BankKey:
        return BankKey(job.acfg, job.batch_size, job.seq_len,
                       max(1, job.microbatch))

    def _try_admit(self, job: FinetuneJob) -> bool:
        if self.n_active >= self.fcfg.max_jobs:
            return False
        placement = None
        if self.router is not None:
            try:
                placement = self.router.route_train(
                    job_hbm_bytes(self.cfg, job, remat=self.fcfg.remat),
                    latency_sensitive=job.latency_sensitive)
            except RuntimeError:
                return False                      # queued until capacity frees
        # TRANSACTIONAL from here: the router charge is the only committed
        # state until the final bookkeeping block, and any failure below
        # must release it (satellite: a mid-admission exception used to
        # strand the charge forever)
        try:
            if self.fault_hook is not None:
                self.fault_hook("train_admit", id(job))
            if job.init_adapter is not None:
                adapter, opt = job.init_adapter, job.init_opt
            else:
                adapter = adapters_lib.init_adapter(
                    self.cfg, job.acfg, jax.random.PRNGKey(job.seed))
                opt = adamw_init(adapter)
            key = self._bank_key(job)
            bank = self._banks.setdefault(
                key, _Bank(key, reserve=self._reserve.get(job.acfg, 0)))
            slot = bank.alloc(adapter, opt)
        except BaseException as e:
            if placement is not None:
                self.router.release(placement)
            if isinstance(e, TransientFault):
                # injected/transient allocation failure: rolled back, job
                # stays queued and retries after backoff
                self._admission_faulted = True
                self.stats["faults"] += 1
                rec = job.health or HealthRecord()
                job.health = rec
                rec.trip(self.stats["train_ticks"],
                         f"admission: {e}", self.health_policy)
                if self._obs is not None:
                    self._obs.event("backoff", engine="finetune",
                                    tick=self.stats["train_ticks"],
                                    tenant=job.name,
                                    reason=f"admission: {e}",
                                    until=rec.next_eligible_tick)
                return False
            raise                                 # rolled back, not swallowed
        bank.slots[slot] = job
        self._place_bank(bank)
        self._slot_of[id(job)] = (key, slot)
        self._step_of[id(job)] = job.start_step
        self._placement[id(job)] = placement
        job.status = "active"
        self.stats["admitted"] += 1
        self.stats["peak_jobs"] = max(self.stats["peak_jobs"], self.n_active)
        if self._obs is not None:
            tick = self.stats["train_ticks"]
            self._obs.event("admit", engine="finetune", tick=tick,
                            tenant=job.name, bank=repr(key.acfg.method),
                            steps=job.steps - job.start_step)
            if job.health is not None and job.health.total_faults:
                self._obs.event("retry", engine="finetune", tick=tick,
                                tenant=job.name,
                                attempts=job.health.total_faults)
            if self.router is not None:
                u = self.router.utilization()
                self._obs.metrics.gauge("router_placements").set(
                    u["placements"])
                self._obs.metrics.gauge("router_committed_bytes").set(
                    u["committed_bytes"])
        return True

    # ------------------------------------------------------------------
    # stepping
    # ------------------------------------------------------------------
    def _mesh_ctx(self):
        """Ambient-mesh context for jitted dispatch (no-op single-device):
        binds the engine mesh while tracing/running a step so the soft
        constraints inside the hot path (``common.constrain``) resolve."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def _place_bank(self, bank: _Bank):
        """``device_put`` a bank's stacked params/opt onto the mesh (slot
        axis over the batch axes). Idempotent — re-run after every alloc so
        growth reallocations land back on their specs."""
        if self.mesh is None:
            return
        from repro.launch import shardings
        bank.params = shardings.put_tree(
            self.mesh, bank.params,
            shardings.bank_state_specs(self.cfg, self.mesh, bank.params))
        bank.opt = shardings.put_tree(
            self.mesh, bank.opt,
            shardings.bank_state_specs(self.cfg, self.mesh, bank.opt))

    def _row_bucket(self, n: int, cap: int) -> int:
        b = 1
        while b < n:
            b *= 2
        return min(b, cap) if cap else b

    def _bank_tick(self, bank: _Bank):
        tick = self.stats["train_ticks"]
        # gather this tick's runnable rows: skip tenants backing off, and
        # contain per-job data-stream failures HERE — one tenant's stream
        # must never unwind the other tenants' tick
        rows = []
        for s, job in enumerate(bank.slots):
            if job is None:
                continue
            if job.health is not None and not job.health.eligible(tick):
                continue                           # SUSPECT: backoff gate
            try:
                b = job.data.batch(self._step_of[id(job)])
            except StreamExhausted as e:
                self._finish_early(job, str(e))
                continue
            except Exception as e:                 # noqa: BLE001 — classified
                self._job_fault(job, tick, e)
                continue
            rows.append((s, job, b))
        if not rows:
            return
        with self._span("compact_gather"):
            R = self._row_bucket(len(rows), bank.cap)
            slots = np.zeros((R,), np.int32)
            mask = np.zeros((R,), bool)
            hyper = {k: np.zeros((R,), np.float32)
                     for k in ("lr", "warmup", "total", "wd", "gnorm")}
            hyper["step"] = np.zeros((R,), np.int32)
            batches = []
            for i, (s, job, b) in enumerate(rows):
                slots[i], mask[i] = s, True
                step = self._step_of[id(job)]
                hyper["step"][i] = step
                hyper["lr"][i] = job.lr
                hyper["warmup"][i] = job.warmup_steps
                hyper["total"][i] = job.schedule_total
                hyper["wd"][i] = job.weight_decay
                hyper["gnorm"][i] = job.max_grad_norm if job.max_grad_norm > 0 \
                    else np.inf
                batches.append(b)
            n = len(batches)

            def stack(*leaves):
                pads = [jnp.zeros_like(leaves[0])] * (R - n)
                return jnp.stack(list(leaves) + pads)

            batch = jax.tree.map(stack, *batches)
        step_fn = _jit_compact_train(self.cfg, bank.key.acfg,
                                     bank.key.microbatch,
                                     self.fcfg.memory_optimized,
                                     self.fcfg.remat, self.mesh)
        with self._span("train_step"), self._mesh_ctx():
            bank.params, bank.opt, metrics = tracecount.dispatch(
                self, "compact_train", (bank.key, R), step_fn,
                self.base, bank.params, bank.opt, batch, jnp.asarray(slots),
                jnp.asarray(mask),
                {k: jnp.asarray(v) for k, v in hyper.items()})
        with self._span("device_sync"):
            losses = np.asarray(metrics["loss"])
            finite = np.asarray(metrics["finite"])
        obs = self._obs
        committed = 0
        with self._span("scatter"):
            for i, (_, job, _b) in enumerate(rows):
                if finite[i]:
                    job.losses.append(float(losses[i]))
                    self._step_of[id(job)] += 1
                    if job.health is not None:
                        job.health.ok(tick)
                    committed += 1
                    if obs is not None:
                        label = job.name or "anon"
                        obs.metrics.counter(
                            "train_steps_total", job=label).inc()
                        obs.metrics.counter(
                            "train_tokens_total", job=label).inc(
                                bank.key.batch * bank.key.seq)
                        obs.metrics.gauge("train_loss", job=label).set(
                            float(losses[i]))
                else:
                    # the in-step probe tripped: the jitted scatter already
                    # dropped this row's commit (its slot kept the last clean
                    # params/opt state), so quarantine checkpoints CLEAN state
                    self.stats["dropped_steps"] += 1
                    self._job_fault(job, tick, NonFiniteFault(
                        f"non-finite loss/grads at step "
                        f"{self._step_of[id(job)]}"))
        self.stats["train_steps"] += committed
        self.stats["compact_rows"] += n
        self.stats["compact_padded"] += R - n
        self.stats["train_tokens"] += committed * bank.key.batch * bank.key.seq

    # ------------------------------------------------------------------
    # fault containment (docs/robustness.md)
    # ------------------------------------------------------------------
    def _job_fault(self, job: FinetuneJob, tick: int, exc: BaseException):
        """Classify one job's fault: transient -> SUSPECT with deterministic
        tick-count backoff (state untouched, retried from the last clean
        step); fatal or retries exhausted -> quarantine."""
        self.stats["faults"] += 1
        rec = job.health or HealthRecord()
        job.health = rec
        reason = f"{type(exc).__name__}: {exc}"
        if classify(exc) == "transient":
            if rec.trip(tick, reason, self.health_policy) == "retry":
                if self._obs is not None:
                    self._obs.event("backoff", engine="finetune", tick=tick,
                                    tenant=job.name, reason=reason,
                                    until=rec.next_eligible_tick)
                return
        else:
            rec.quarantine(tick, reason)
        self._quarantine_job(job)

    def _quarantine_job(self, job: FinetuneJob):
        """Fatal path: checkpoint the job's last CLEAN state (best effort —
        a failing checkpoint write must not block retirement), then retire
        it, releasing its bank slot and router charge."""
        if self.quarantine_dir is not None:
            try:
                self.checkpoint_job(job, self.quarantine_dir)
            except Exception as e:                 # noqa: BLE001
                if job.health is not None:
                    job.health.history.append(
                        (self.stats["train_ticks"], "quarantined",
                         f"quarantine checkpoint failed: {e}"))
        self.stats["quarantined"] += 1
        if self._obs is not None:
            last = job.health.last_transition() if job.health else None
            self._obs.event("quarantine", engine="finetune",
                            tick=self.stats["train_ticks"], tenant=job.name,
                            scope="job",
                            reason=last[2] if last else "quarantined")
        self.retire(job, status="quarantined")

    def _finish_early(self, job: FinetuneJob, reason: str):
        """Stream ran dry inside the step budget: complete the job as
        ``finished_early`` — checkpointed (when a quarantine_dir is set),
        charges released, result handed back — instead of raising out of
        train_tick."""
        if self.quarantine_dir is not None:
            try:
                self.checkpoint_job(job, self.quarantine_dir)
            except Exception:                      # noqa: BLE001 — best effort
                pass
        if job.health is not None:
            job.health.retire(self.stats["train_ticks"], reason)
        self.stats["finished_early"] += 1
        self.retire(job, status="finished_early")

    def trace_domain(self) -> tracecount.TraceDomain:
        """Legal jit keys (analysis 'buckets' pass): one compile per
        (bank key, row bucket) with the bucket a power of two — capacity
        doubles, membership gathers into power-of-two buckets, so any other
        row count compiling is a hot-path recompile."""
        d = tracecount.TraceDomain()
        d.declare("compact_train",
                  predicate=lambda key: (isinstance(key, tuple) and
                                         len(key) == 2 and key[1] >= 1 and
                                         key[1] & (key[1] - 1) == 0))
        return d

    def train_tick(self) -> bool:
        """Admit due jobs, run one optimizer step for every active job
        (one compact call per non-empty bank), retire exhausted jobs.
        Returns True while jobs remain active or queued. Per-job faults are
        contained (health machine + quarantine, docs/robustness.md) — one
        tenant's stream/NaN/allocation failure never unwinds the tick."""
        tick = self.stats["train_ticks"]
        obs = self._obs
        t0 = obs.tick_start("finetune") if obs is not None else 0.0
        self._admission_faulted = False
        admitted_any = False
        backing_off = 0
        with self._span("admit"):
            for job in list(self._queue):
                if job.health is not None and not job.health.active:
                    # admission retries exhausted: reject without crashing
                    self._queue.remove(job)
                    job.status = "quarantined"
                    self.stats["quarantined"] += 1
                    self.finished.append(job)
                    if obs is not None:
                        obs.event("quarantine", engine="finetune", tick=tick,
                                  tenant=job.name, scope="job",
                                  reason="admission retries exhausted")
                    continue
                if job.health is not None and not job.health.eligible(tick):
                    backing_off += 1
                    continue                       # SUSPECT: retry later
                if self._try_admit(job):
                    self._queue.remove(job)
                    admitted_any = True
        if obs is not None and backing_off:
            obs.metrics.counter("train_backoff_skips_total").inc(backing_off)
        if self._queue and not self._slot_of and not admitted_any \
                and not self._admission_faulted and not backing_off:
            raise AdmissionStall(
                f"{len(self._queue)} job(s) can never be admitted "
                f"(no free capacity and nothing running)")
        for bank in self._banks.values():
            self._bank_tick(bank)
        self.stats["train_ticks"] += 1
        for job in [j for (key, s) in list(self._slot_of.values())
                    for j in [self._banks[key].slots[s]]
                    if self._step_of[id(j)] >= j.steps]:
            self.retire(job)
        if self.debug:
            from repro.faults.audit import finetune_conservation
            errs = finetune_conservation(self)
            if errs:
                raise AssertionError("conservation audit failed after "
                                     f"train tick {tick}:\n  "
                                     + "\n  ".join(errs))
        if obs is not None:
            obs.tick_end("finetune", tick, t0)
        return self.pending()

    def drain_events(self, *, client=None, kind=None) -> list:
        """Client-visible event feed (docs/observability.md): drain this
        engine's structured events — admit/retire/backoff/retry/quarantine/
        compile — optionally filtered to one tenant (``client`` matches the
        job's ``name``) or one ``kind``. Returns [] when no telemetry is
        attached; draining is destructive for the matched events only."""
        if self._obs is None:
            return []
        if client is None:
            return self._obs.drain_events(kind=kind, engine="finetune")
        return self._obs.drain_events(client=client, kind=kind,
                                      engine="finetune")

    def run(self) -> List[FinetuneJob]:
        """Drive all queued/active jobs to their step budgets."""
        while self.train_tick():
            pass
        out, self.finished = self.finished, []
        return out

    # ------------------------------------------------------------------
    # job state, retirement, checkpointing
    # ------------------------------------------------------------------
    def job_state(self, job: FinetuneJob):
        """(adapter, opt, next_step) for an ACTIVE job — a device-side
        snapshot of its bank slot (used for checkpoints and inspection)."""
        key, slot = self._slot_of[id(job)]
        adapter, opt = self._banks[key].read(slot)
        return adapter, opt, self._step_of[id(job)]

    def retire(self, job: FinetuneJob, *, status: str = "finished") -> JobResult:
        """Remove a job from service (explicit mid-run leave, budget
        exhaustion, ``finished_early`` stream end, or quarantine) and hand
        back its state. The bank slot frees for the next admission; the
        stale row is never read again; the router charge releases."""
        adapter, opt, step = self.job_state(job)
        key, slot = self._slot_of.pop(id(job))
        self._banks[key].slots[slot] = None
        del self._step_of[id(job)]
        placement = self._placement.pop(id(job), None)
        if placement is not None:
            self.router.release(placement)
        job.status = status
        if job.health is not None and status != "quarantined":
            job.health.retire(self.stats["train_ticks"], status)
        job.result = JobResult(adapter=adapter, opt=opt, step=step,
                               losses=list(job.losses))
        self.finished.append(job)
        self.stats["retired"] += 1
        if self._obs is not None:
            self._obs.event("retire", engine="finetune",
                            tick=self.stats["train_ticks"], tenant=job.name,
                            status=status, steps=step)
            if self.router is not None:
                u = self.router.utilization()
                self._obs.metrics.gauge("router_placements").set(
                    u["placements"])
                self._obs.metrics.gauge("router_committed_bytes").set(
                    u["committed_bytes"])
        return job.result

    def checkpoint_job(self, job: FinetuneJob, directory: str) -> str:
        """Write an ACTIVE job's adapter + optimizer state (resume with
        ``checkpoint.restore_job_state`` + ``FinetuneJob(init_adapter=...,
        init_opt=..., start_step=...)``)."""
        from repro.checkpoint import save_job_state
        adapter, opt, step = self.job_state(job)
        return save_job_state(directory, step, adapter, opt,
                              name=job.name or "job")

    # ------------------------------------------------------------------
    # whole-engine crash recovery (docs/robustness.md)
    # ------------------------------------------------------------------
    def _job_fields(self, job: FinetuneJob) -> dict:
        return dict(acfg=job.acfg, data=job.data, batch_size=job.batch_size,
                    seq_len=job.seq_len, steps=job.steps, lr=job.lr,
                    weight_decay=job.weight_decay,
                    warmup_steps=job.warmup_steps,
                    total_steps=job.total_steps,
                    max_grad_norm=job.max_grad_norm,
                    microbatch=job.microbatch, name=job.name, seed=job.seed,
                    latency_sensitive=job.latency_sensitive)

    def engine_state(self) -> dict:
        """A picklable snapshot of every tenant: active jobs carry their
        device-side adapter/optimizer state (as numpy), their global step,
        loss history, health record and data-stream object (streams pickle
        with their cursor — see ``faults.FaultyStream``); queued and
        finished jobs ride along. Feed to ``checkpoint.save_engine_state``;
        restore into a FRESH engine (same spec + base) with
        ``load_engine_state`` — every job resumes its uninterrupted
        trajectory bitwise (the step counter drives both the schedule and
        the deterministic stream). Single-device engines only."""
        if self.mesh is not None:
            raise NotImplementedError(
                "whole-engine checkpointing is single-device (mesh=None)")
        tonp = lambda t: (None if t is None else
                          jax.tree.map(np.asarray, jax.device_get(t)))
        active = []
        for jid, (key, slot) in self._slot_of.items():
            job = self._banks[key].slots[slot]
            adapter, opt, step = self.job_state(job)
            active.append(dict(self._job_fields(job),
                               init_adapter=tonp(adapter), init_opt=tonp(opt),
                               start_step=step, losses=list(job.losses),
                               status=job.status, health=job.health))
        def _rec(job):
            return dict(self._job_fields(job),
                        init_adapter=tonp(job.init_adapter),
                        init_opt=tonp(job.init_opt),
                        start_step=job.start_step, losses=list(job.losses),
                        status=job.status, health=job.health,
                        result=None if job.result is None else dict(
                            adapter=tonp(job.result.adapter),
                            opt=tonp(job.result.opt), step=job.result.step,
                            losses=list(job.result.losses)))
        return {"active": active,
                "queued": [_rec(j) for j in self._queue],
                "finished": [_rec(j) for j in self.finished],
                "stats": dict(self.stats)}

    def load_engine_state(self, state: dict):
        """Restore an ``engine_state()`` snapshot into this freshly
        constructed engine. Active jobs re-enter the queue (in their
        original admission order) as resume jobs — the next ``train_tick``
        re-routes their charges and re-allocates bank slots; slot indices
        may differ but the math is slot-invariant, so each tenant's
        trajectory continues bit-for-bit."""
        if self._slot_of or self._queue or self.finished:
            raise RuntimeError("load_engine_state needs a freshly "
                               "constructed engine (no jobs)")
        def _job(rec):
            r = dict(rec)
            result = r.pop("result", None)
            losses = r.pop("losses", [])
            status = r.pop("status", "queued")
            job = FinetuneJob(**{k: v for k, v in r.items() if k != "health"})
            job.losses = list(losses)
            job.status = "queued" if status == "active" else status
            job.health = rec.get("health")
            if result is not None:
                job.result = JobResult(**result)
            return job
        for rec in state["active"]:
            self._queue.append(_job(rec))
        for rec in state["queued"]:
            self._queue.append(_job(rec))
        for rec in state["finished"]:
            self.finished.append(_job(rec))
        self.stats.update(state["stats"])

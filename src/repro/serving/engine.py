"""Continuous-batching multi-client serving engine (paper §3.7 / §4.4).

Drives real model execution for a bank of inference clients that share one
frozen base. The engine realizes the paper's opportunistic-batching claim —
"requests batched at the first layer need not batch at later layers" — as a
live system rather than an offline simulation:

Architecture
------------
* **Slots.** Each client owns ``max_batch_per_client`` sequence slots backed
  by its rows of the bank KV/state cache. A request occupies one slot per
  prompt row for its lifetime; slots free the moment their request finishes
  and are re-admitted from the queue on the next tick — not after the whole
  bank drains (mid-stream join/leave).
* **Bank registry (heterogeneous PEFT methods).** One engine may hold
  SEVERAL serving banks keyed by AdapterConfig — pass ``acfg`` /
  ``client_bank`` as matching sequences — mirroring
  ``training.FinetuneEngine``'s bank grouping: LoRA, IA3 and prefix
  clients (or same-method banks of different rank) served concurrently
  over one frozen base. Clients carry GLOBAL ids in bank concatenation
  order; caches, the page allocator and slot bookkeeping stay keyed by
  the global id (the KV layout is method-independent) while admission
  prefills through the client's own bank's jitted step and ONE compacted
  decode tick carries per-row method ids (see "per-row-method contract"
  below). Mixed banks require the paged layout + compacted decode; a
  router is charged each bank's resident adapter bytes
  (``PlacementRouter.route_bank`` / ``release_banks()``).
* **Per-row-method contract.** In a mixed compacted tick, LoRA rows keep
  the SGMV path (rows of other banks get dead adapter ids, so the kernel
  emits zeros for them), IA3 scales and prefix K/V are gathered per row
  with clamped bank-local ids, and EVERY application — including the
  prefix-attention add inside the model — is merged through a
  ``jnp.where`` on the row's membership mask: a select preserves
  non-member rows' bits exactly, which is what makes each client's stream
  in a mixed batch byte-identical to its solo single-method run
  (tests/test_mixed_serving.py).
* **KV layout.** ``ServeConfig.page_block = 0`` keeps dense fixed-depth
  (``max_seq``) cache rows per slot. ``page_block > 0`` switches to the
  PAGED layout: the device holds ONE global flat pool of
  ``page_block``-token pages per KV leaf; each client owns the page RANGE
  ``[c*P, (c+1)*P)`` (``pool_pages`` = P per client) and the engine runs a
  host-side page allocator — prompt pages are assigned at admission, one
  page is assigned as a slot's decode position crosses each block
  boundary, and a finished request's pages return to the pool for the next
  occupant. The device sees the allocator only through the ``block_tbl``
  cache leaf (global page ids, pushed before prefill/decode whenever it
  changed); attention reads pages in place through the table-aware
  ``kernels/decode_attn`` kernel. ``kv_quant=True`` stores int8 KV entries
  + per-head f32 scales and composes with paging. The paged layout tracks
  the dense one within float tolerance (the kernel's blocked online
  softmax re-associates reductions) with identical greedy streams.
* **Compute-proportional decode.** With the paged layout the engine
  defaults to the COMPACTED decode tick (``compact_decode``; the masked
  bank-wide step stays as the dense-layout path and the
  ``compact_decode=False`` ablation): the actively decoding
  (client, slot) rows are gathered across clients into a dense batch
  (bucketed to a few static sizes to bound recompiles), run through the
  model once — per-row LoRA via ``kernels/sgmv``, attention via the paged
  kernel — and scattered back under the row mask. FLOPs and HBM traffic
  scale with ACTIVE tokens, not provisioned slots; outputs are
  byte-identical to the masked step under every tick policy (the masked
  step lowers to the same flattened computation through the kernels'
  custom_vmap rules). Cache buffers are donated into the jitted steps, so
  a tick updates the bank cache in place instead of copying it.
* **Admission.** A per-engine FIFO queue. A request is admitted when (a) its
  client has enough free slots, (b) its context fits the cache depth,
  (c) under paging, the client pool has enough unreserved pages for the
  full context (reserved up front so a mid-flight sequence can never
  starve; physically assigned lazily as tokens are produced), and (d) the
  optional ``PlacementRouter`` finds it a §3.4 placement (capacity is
  released on finish). The router is charged for what the layout actually
  pins: the dense engine charges a full ``max_seq``-deep slot row, the
  paged engine only the context rounded up to whole pages — the admission
  headroom that motivates paging. Admission triggers the *masked
  single-client prefill* (``symbiosis.make_client_prefill``): one model
  execution for the admitted client, scattered into the bank cache under a
  slot mask — the seed engine instead ran a bank-wide prefill, paying C×
  base compute per admitted request.
* **Cross-client compacted prefill.** On paged attention engines, ALL of
  a tick's admissions — across clients and banks — gather into ONE
  jit-bucketed ragged batch (``symbiosis.make_compact_prefill``, the
  prefill analogue of the compacted decode tick): each row carries its own
  prompt right-padded to a shared suffix bucket, its true ``lengths``
  entry and per-row (client, adapter, bank) ids, so one model execution
  per TICK replaces one per client per tick. Byte-identical to sequential
  per-request admission — rows are independent (per-row positions, causal
  mask, last-token logit gather, length-bounded pool writes). Dense-layout
  attention engines keep the same-client masked ragged batch (the paged
  fold needs page pools); recurrent families and ``ragged_prefill=False``
  keep per-request calls.
* **Shared-prefix page reuse (docs/prefix_cache.md).** Prompt prefixes
  are content-hashed block by block into a refcounted host-side index
  (``serving.prefix_cache.PrefixIndex``): an admission whose prompt
  prefix was already prefilled under the SAME adapter maps the published
  read-only pages into its block table (refs++), CoW-copies a matched
  partial tail page, and prefills only its suffix — the compacted prefill
  attends to the mapped pages as external K/V lanes. Retirement releases
  references; a page recycles only at refcount zero. The router is
  charged only newly-allocated pages. Byte-identical by construction:
  published pages hold exactly the bytes the row's own prefill would have
  written (same adapter, same tokens, same positions), asserted against
  solo serving in tests/test_prefix_cache.py. ``prefix_cache=False``
  disables reuse; int8-quantized pools opt out automatically.
* **Tick API.** ``service_tick()`` runs ONE admission+decode+retire round;
  ``run()`` loops it to completion. ``training.SymbiosisEngine``
  interleaves these ticks with a ``FinetuneEngine``'s train steps so
  inference and fine-tuning time-share the same resident base (§4.4).
* **Tick loop.** Every tick the scheduler policy (``core.scheduler.
  TickPolicy`` — lockstep / nolockstep / opportunistic) picks which *ready*
  clients join the batched decode (``symbiosis.make_masked_decode_step``);
  slots outside the tick keep their cache and position untouched inside the
  jitted step.
* **Sampling.** Greedy, temperature and top-k sampling, seeded per request
  (np.random.Generator keyed on the request's sampling seed + client id),
  so draws depend only on the request's own token stream.
* **Policy-invariance contract.** The policy (and any interleaving of other
  clients) only changes WHICH ready clients execute a given tick, never the
  math of a sequence's own stream — outputs are byte-identical across
  policies and to serving each request alone (paper: "the output with
  Symbiosis is exactly identical to that of the baseline"); asserted in
  tests/test_serving_engine.py.

For latency realism the engine also reports a scheduler-simulated timeline
(``simulate_policy``) calibrated with measured per-op costs.

Seed-engine ablation knobs: ``bank_prefill=True`` restores the bank-wide
prefill path and ``max_inflight_per_client=1`` the one-request-per-client
admission rule — used by benchmarks/bench_multiclient.py to quantify what
continuous batching buys over the seed behaviour.

Machine-checked invariants (docs/invariants.md): the engine's hot-path
contracts — cache pools donated and written in place, jitted steps
compiling only the closed bucket set declared by ``trace_domain()``, no
base-sized collectives, client isolation — are enforced by
``python -m repro.analysis`` and the tier-1 trace guard in
tests/conftest.py; jitted dispatch routes through
``repro.analysis.tracecount.dispatch``.

Observability (docs/observability.md): pass ``obs=repro.obs.Obs()`` to get
tick-phase spans (``jax.profiler`` named scopes + latency histograms),
per-tenant metrics (queue-wait / TTFT / inter-token / end-to-end latency,
token and page counters, HBM charges) and the client-visible event log
(``drain_events(client=...)`` — admissions, retirements, backoff/retry,
quarantines, bank growth, compiles). Telemetry is bitwise-invisible to the
engine's outputs, adds no device syncs inside the tick (host timestamps at
tick/phase boundaries only) and no jit keys; ``obs=None`` (default) is a
hard no-op. Per-request latency is always recorded on the request itself
(``submit_t``/``admit_t``/``first_token_t``/``finish_t`` +
``queue_wait``/``ttft``/``e2e_latency`` properties), and fault handling
always appends to ``Request.fault_history``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
import warnings
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import tracecount
from repro.config import ModelConfig, ServeConfig, DENSE, MOE, VLM
from repro.core import adapters as adapters_lib
from repro.core import symbiosis
from repro.core.engine_spec import EngineSpec
from repro.core.scheduler import ClientSpec, TickPolicy, simulate
from repro.faults.health import (AdmissionStall, HealthPolicy, HealthRecord,
                                 HealthState, classify)
from repro.faults.plan import TransientFault
from repro.kernels.decode_attn.decode_attn import pages_read
from repro.serving.prefix_cache import PrefixIndex, sharable_tokens

# disabled-telemetry span: one shared, reusable null context manager — the
# tick loop's `with self._span(name)` costs a function call and nothing
# else, and no timing machinery (repro.obs, jax.profiler) is imported
_NULL_CTX = contextlib.nullcontext()


def _null_span(name: str):
    return _NULL_CTX


def _pin_serving(fn, cfg, scfg, mesh, *, cache_arg=2):
    """Sharded hot path: pin the donated cache tree to its canonical specs
    on the way IN and OUT of a jitted step (``launch.shardings.
    serving_cache_constrain``). Donated state then keeps ONE placement
    across ticks — no per-tick resharding copies, no executable churn —
    and the compiler is told the client/page partition survives the step,
    so compaction never round-trips through a replicated (base-sized)
    layout. ``mesh=None`` returns ``fn`` untouched. Steps return
    ``(*outputs, caches)`` — the probed compact decode carries an extra
    per-row finite output between logits and caches."""
    if mesh is None:
        return fn
    from repro.launch import shardings

    def pinned(*a):
        a = list(a)
        a[cache_arg] = shardings.serving_cache_constrain(
            cfg, scfg, mesh, a[cache_arg])
        *out, caches = fn(*a)
        return (*out,
                shardings.serving_cache_constrain(cfg, scfg, mesh, caches))

    return symbiosis.program_named(pinned, fn.__name__)


# Jitted step builders are memoized on the (frozen, hashable) configs so
# every engine instance over the same model shares one compile cache —
# constructing an engine is cheap and benchmarks don't re-pay compilation.
# ``mesh`` joins the key (jax Meshes hash by shape + axis names + devices):
# a sharded engine gets its own jitted wrapper, keeping the per-engine
# trace accounting clean. The cache tree (arg 2) is DONATED in every step
# that replaces it: the engine always rebinds ``self.caches`` to the
# result, and donation lets XLA update the (potentially multi-GB) bank
# cache in place instead of copying it once per tick — without it,
# per-tick cost grows with bank size no matter how few slots decode.
@functools.lru_cache(maxsize=None)
def _jit_client_prefill(cfg, acfg, scfg, mesh=None):
    return jax.jit(_pin_serving(symbiosis.make_client_prefill(cfg, acfg, scfg),
                                cfg, scfg, mesh),
                   donate_argnums=2)


@functools.lru_cache(maxsize=None)
def _jit_masked_decode(cfg, acfg, scfg, mesh=None):
    return jax.jit(_pin_serving(
        symbiosis.make_masked_decode_step(cfg, acfg, scfg), cfg, scfg, mesh),
                   donate_argnums=2)


@functools.lru_cache(maxsize=None)
def _jit_bank_prefill(cfg, acfg, scfg, mesh=None):
    return jax.jit(_pin_serving(
        symbiosis.make_multi_client_prefill(cfg, acfg, scfg), cfg, scfg, mesh))


@functools.lru_cache(maxsize=None)
def _jit_compact_decode(cfg, acfg, scfg, mesh=None, probe=False):
    return jax.jit(_pin_serving(
        symbiosis.make_compact_decode_step(cfg, acfg, scfg, probe=probe),
        cfg, scfg, mesh),
                   donate_argnums=2)


# The prefill analogue of the compacted decode tick (ISSUE 10 tentpole):
# one jitted program per (row-bucket-independent) ext_blocks value — the
# row bucket and padded suffix length are ordinary shape-keyed recompiles
# inside the one builder, while ext_blocks (how many leading block-table
# entries each row attends to as read-only shared-prefix lanes) must join
# the builder key because it changes the traced program structure.
@functools.lru_cache(maxsize=None)
def _jit_compact_prefill(cfg, acfg, scfg, mesh=None, ext_blocks=0):
    return jax.jit(_pin_serving(
        symbiosis.make_compact_prefill(cfg, acfg, scfg, probe=True,
                                       ext_blocks=ext_blocks),
        cfg, scfg, mesh),
                   donate_argnums=2)


# Copy-on-write page duplication for shared-prefix tails: one tiny donated
# dispatch copying a single pool page (every layer's lanes at once — the
# stored leaves carry an explicit layer axis). src/dst are traced scalars,
# so all copies share ONE compile.
@functools.lru_cache(maxsize=None)
def _jit_page_copy(cfg, scfg, mesh=None):
    fn = symbiosis.make_page_copy(cfg, scfg)
    if mesh is not None:
        from repro.launch import shardings
        inner = fn

        def fn(caches, src, dst):
            caches = shardings.serving_cache_constrain(cfg, scfg, mesh, caches)
            return shardings.serving_cache_constrain(
                cfg, scfg, mesh, inner(caches, src, dst))

        symbiosis.program_named(fn, inner.__name__)
    return jax.jit(fn, donate_argnums=0)


@dataclasses.dataclass
class SamplingParams:
    """Per-request sampling config. ``seed`` keys the request's private RNG:
    draws are consumed in token order of the request's own stream, so
    sampled outputs (not just greedy) are schedule/policy-invariant."""
    method: str = "greedy"            # greedy | temperature | top_k
    temperature: float = 1.0
    top_k: int = 0
    seed: int = 0


@dataclasses.dataclass
class BankAdmission:
    """Handle for one ``admit_bank()`` call: the bank it joined (or
    created), the new clients' global ids, and the router charge to release
    at ``retire_bank()``."""
    bank_id: int
    client_ids: List[int]
    placement: object = None


@dataclasses.dataclass(eq=False)       # identity eq: queues hold np arrays
class Request:
    client_id: int
    prompt: Optional[np.ndarray]            # [B, S] int32 (B sequence slots)
    max_new_tokens: int = 16
    latency_sensitive: bool = True
    sampling: Optional[SamplingParams] = None   # None -> greedy
    arrive_tick: int = 0                    # earliest tick admission may see it
    # stream-backed prompt delivery (docs/robustness.md): submit with
    # prompt=None and a prompt_stream exposing fetch(); the engine resolves
    # the prompt at admission, where delivery faults back the client off
    # (transient) or reject the request (exhaustion)
    prompt_stream: Optional[object] = None
    # filled by the engine:
    generated: Optional[np.ndarray] = None  # [B, max_new_tokens]
    submit_t: float = 0.0                   # perf_counter at submit()
    admit_t: float = 0.0                    # ... at successful admission
    first_token_t: float = 0.0              # ... when the first token sampled
    finish_t: float = 0.0                   # ... at retirement
    # lifecycle (docs/robustness.md): ok | quarantined (non-finite logits —
    # terminated, slots/pages/charges freed) | rejected (its client was
    # quarantined before this request ran, or its prompt stream ran dry)
    status: str = "ok"
    # client-visible fault trajectory: (tick, kind, reason) tuples, kind in
    # {backoff, retry, quarantine, rejected} — docs/observability.md
    fault_history: List[tuple] = dataclasses.field(default_factory=list)

    @property
    def queue_wait(self) -> Optional[float]:
        """Seconds from submit to admission (None until admitted)."""
        return self.admit_t - self.submit_t if self.admit_t else None

    @property
    def ttft(self) -> Optional[float]:
        """Seconds from submit to the first sampled token."""
        return (self.first_token_t - self.submit_t
                if self.first_token_t else None)

    @property
    def e2e_latency(self) -> Optional[float]:
        """Seconds from submit to retirement (None until finished)."""
        return self.finish_t - self.submit_t if self.finish_t else None


class ServingEngine:
    """One base model continuously serving one or more banks of adapter
    clients.

    BANK REGISTRY (heterogeneous PEFT methods, mirroring
    ``training.FinetuneEngine``'s bank grouping): pass ``acfg`` as a
    sequence of AdapterConfigs and ``client_bank`` as the matching sequence
    of client-stacked adapter trees — e.g. a LoRA bank, an IA3 bank and a
    prefix bank served CONCURRENTLY by one engine over one frozen base.
    Clients get GLOBAL ids in bank concatenation order (bank 0's clients
    first); the KV caches, page allocator and slot bookkeeping stay keyed
    by the global id (the cache layout is method-independent), while
    admission routes each request's prefill through its own bank's jitted
    step and the compacted decode tick carries per-row method ids — LoRA
    rows keep the SGMV path, IA3/prefix rows get per-row gathers, every
    application gated by a membership select (see
    ``symbiosis.make_compact_decode_step``'s mixed mode). A mixed batch is
    byte-identical to each client's solo single-method run. Mixed banks
    require the paged KV layout (the compacted tick is the only decode
    path that can carry per-row methods); an attached ``PlacementRouter``
    is charged each bank's resident adapter bytes (``route_bank``),
    released via ``release_banks()``.

    CONSTRUCTION (``core.engine_spec.EngineSpec``)::

        spec = EngineSpec(cfg=cfg, banks=(BankSpec("lora8", lora, 4),),
                          serve=scfg, mesh=None)
        engine = ServingEngine(spec, base_params, banks)

    where ``banks`` is one client-stacked adapter tree per ``spec.banks``
    entry (a bare tree for a single bank). ``spec.mesh`` set to a
    ``jax.sharding.Mesh`` shards the engine: the frozen base by
    ``launch.shardings.base_param_specs`` (or replicated with
    ``spec.replicate_base``), caches/page pools/banks with their
    client/page axes over the batch axes; ``mesh=None`` is byte-identical
    to today's single-device engine.

    FAULT CONTAINMENT (docs/robustness.md): per-client health records,
    a compiled-in finite probe on prefill and decode logits, quarantine of
    faulty requests/clients with full page/charge release, transactional
    (rollback-exact) admission, and whole-engine ``engine_state()`` /
    ``load_engine_state()`` crash recovery — survivors stay bitwise
    identical to a never-faulted run.

    DEPRECATED: the parallel-sequence positional form
    ``ServingEngine(cfg, acfg, scfg, base_params, client_bank, ...)``
    still works but emits a ``DeprecationWarning`` — migrate to the
    EngineSpec form above (see docs/sharding.md)."""

    def __init__(self, spec, *args, **kwargs):
        if isinstance(spec, EngineSpec):
            self._init_from_spec(spec, *args, **kwargs)
        else:
            warnings.warn(
                "ServingEngine(cfg, acfg, scfg, base_params, client_bank) is "
                "deprecated; construct an EngineSpec and call "
                "ServingEngine(spec, base_params, banks) (docs/sharding.md)",
                DeprecationWarning, stacklevel=2)
            self._setup(spec, *args, **kwargs)

    def _init_from_spec(self, spec: EngineSpec, base_params, banks, *,
                        router=None, policy: Optional[str] = None,
                        bank_prefill: bool = False,
                        max_inflight_per_client: Optional[int] = None,
                        compact_decode: Optional[bool] = None,
                        ragged_prefill: Optional[bool] = None,
                        prefix_cache: Optional[bool] = None,
                        health_policy: Optional[HealthPolicy] = None,
                        debug: bool = False, fault_hook=None, obs=None):
        if spec.serve is None:
            raise ValueError("ServingEngine needs EngineSpec.serve")
        if not spec.banks:
            raise ValueError("ServingEngine needs at least one BankSpec")
        banks = list(banks) if isinstance(banks, (tuple, list)) else [banks]
        if len(banks) != len(spec.banks):
            raise ValueError(f"{len(banks)} adapter trees for "
                             f"{len(spec.banks)} declared banks")
        for bs, tree in zip(spec.banks, banks):
            k = jax.tree.leaves(tree)[0].shape[0]
            if k != bs.capacity:
                raise ValueError(f"bank {bs.name!r}: adapter tree holds {k} "
                                 f"clients, spec capacity is {bs.capacity}")
        single = len(spec.banks) == 1
        self._setup(spec.cfg,
                    spec.banks[0].acfg if single else spec.bank_cfgs(),
                    spec.serve, base_params,
                    banks[0] if single else banks,
                    max_batch_per_client=spec.max_batch_per_client,
                    router=router, policy=policy, bank_prefill=bank_prefill,
                    max_inflight_per_client=max_inflight_per_client,
                    compact_decode=compact_decode,
                    ragged_prefill=ragged_prefill,
                    prefix_cache=prefix_cache,
                    health_policy=health_policy, debug=debug,
                    fault_hook=fault_hook, obs=obs,
                    mesh=spec.mesh, replicate_base=spec.replicate_base,
                    bank_repl=tuple(b.placement == "replicated"
                                    for b in spec.banks),
                    spec=spec)

    def _setup(self, cfg: ModelConfig, acfg, scfg: ServeConfig,
               base_params, client_bank, *, max_batch_per_client: int = 4,
               router=None, policy: Optional[str] = None,
               bank_prefill: bool = False,
               max_inflight_per_client: Optional[int] = None,
               compact_decode: Optional[bool] = None,
               ragged_prefill: Optional[bool] = None,
               prefix_cache: Optional[bool] = None,
               health_policy: Optional[HealthPolicy] = None,
               debug: bool = False, fault_hook=None, obs=None,
               mesh=None, replicate_base: bool = False,
               bank_repl: tuple = (), spec: Optional[EngineSpec] = None):
        self.cfg, self.acfg, self.scfg = cfg, acfg, scfg
        self.spec = spec
        self.mesh = mesh
        self._replicate_base = replicate_base
        self._bank_repl = bank_repl
        if mesh is not None:
            from repro.launch import shardings
            # idempotent + identity-preserving: SymbiosisEngine.from_spec
            # shards the base ONCE and both engines re-run this as a no-op,
            # keeping the shared-base leaf-identity check intact
            base_params = shardings.shard_base_params(
                cfg, mesh, base_params, replicate=replicate_base)
        self.base = base_params
        self.bank = client_bank
        self._mixed = isinstance(acfg, (tuple, list))
        if self._mixed:
            if not isinstance(client_bank, (tuple, list)) or \
                    len(client_bank) != len(acfg):
                raise ValueError("mixed-method serving: client_bank must be "
                                 "a sequence of adapter trees matching acfg")
            self.bank_cfgs = tuple(acfg)
            self.banks = list(client_bank)
            sizes = [jax.tree.leaves(b)[0].shape[0] for b in self.banks]
        else:
            self.bank_cfgs = (acfg,)
            self.banks = [client_bank]
            sizes = [jax.tree.leaves(client_bank)[0].shape[0]]
        self.n_clients = sum(sizes)
        # global client id -> (bank id, index within the bank's adapter tree)
        self._method_of = np.repeat(np.arange(len(sizes)), sizes).astype(np.int32)
        self._local_of = np.concatenate(
            [np.arange(s) for s in sizes]).astype(np.int32)
        self.max_b = max_batch_per_client
        self.router = router
        self.policy = TickPolicy(policy or scfg.policy)
        self.bank_prefill = bank_prefill
        if bank_prefill and max_inflight_per_client not in (None, 1):
            raise ValueError("bank_prefill replaces the whole client cache "
                             "slice; it requires max_inflight_per_client=1")
        self.max_inflight = 1 if bank_prefill else max_inflight_per_client
        cache_kw = symbiosis.serve_cache_kwargs(cfg, scfg)
        self._paged = "page_block" in cache_kw
        self._quant = bool(cache_kw.get("quant"))
        if self._mixed and not self._paged:
            raise ValueError(
                "mixed-method serving banks require the paged KV layout "
                "(ServeConfig.page_block > 0): only the compacted decode "
                "tick can carry per-row methods")
        if self._mixed and compact_decode is False:
            raise ValueError("mixed-method serving banks decode through the "
                             "compacted per-row-method step; the masked "
                             "bank-wide ablation is single-method only")
        if self._mixed and bank_prefill:
            raise ValueError("bank_prefill is a single-method dense-layout "
                             "ablation")
        # per-bank HBM charges: the router accounts each bank's resident
        # adapter weights (released via release_banks()); single-bank
        # engines keep the pre-registry accounting (KV-only) unchanged
        self._bank_placements = []
        if router is not None and self._mixed:
            try:
                for m, a in enumerate(self.bank_cfgs):
                    _, nbytes = adapters_lib.adapter_bytes(cfg, a)
                    self._bank_placements.append(
                        router.route_bank(nbytes * sizes[m]))
            except RuntimeError:
                # a later bank didn't fit: refund the banks already
                # committed, or their charges leak (no engine object ever
                # exists to release them through)
                self.release_banks()
                raise
        if self._paged:
            if bank_prefill:
                raise ValueError("bank_prefill replaces whole cache slices; "
                                 "it is a dense-layout-only ablation")
            self._blk = scfg.page_block
            self._n_blocks = -(-scfg.max_seq // self._blk)
            self._pool_pages = scfg.pool_pages or max_batch_per_client * self._n_blocks
            cache_kw["pool_pages"] = self._pool_pages
            # host-side page allocator: per-client free list + reservation
            # count (pages promised to in-flight requests but not yet
            # assigned), per-slot assigned pages, per-slot next write pos,
            # and the block-table mirror pushed to the device when dirty.
            # Page ids are GLOBAL (client c owns [c*P, (c+1)*P) of the one
            # flat device pool — see symbiosis.init_client_caches); the
            # per-client free lists keep ISSUE-2 admission semantics
            # (per-client pool backpressure) as an allocator convention.
            self._free_pages = [list(range(c * self._pool_pages,
                                           (c + 1) * self._pool_pages))
                                for c in range(self.n_clients)]
            self._reserved = [0] * self.n_clients
            self._slot_pages: Dict[tuple, List[int]] = {}
            self._wpos = np.zeros((self.n_clients, self.max_b), np.int64)
            # unmapped table entries hold an OUT-OF-RANGE sentinel: under
            # the global pool a zero would alias client 0's first page, and
            # any stray write through a stale entry would corrupt it; the
            # sentinel makes such writes scatter-drop (reads through it are
            # clamped and always position-masked). A fixed huge constant —
            # NOT n_clients * pool_pages, which would become a valid page id
            # the moment admit_bank() grows the pool.
            self._tbl_oob = np.int32(1 << 30)
            self._tbl = np.full((self.n_clients, self.max_b, self._n_blocks),
                                self._tbl_oob, np.int32)
            self._tbl_dirty = True
            self._resv_of: Dict[int, int] = {}
            # shared-prefix page reuse (ISSUE 10, docs/prefix_cache.md):
            # the content-keyed refcounted index over published prompt-
            # prefix pages, the per-slot lists of REF-HELD pages (a slot's
            # table = shared pages first, then its exclusive _slot_pages),
            # the per-slot suffix start recorded at admission for the tick's
            # compacted prefill, and the CoW page copies queued for dispatch
            # just before that prefill runs
            self._prefix_index = PrefixIndex()
            self._slot_shared: Dict[tuple, List[int]] = {}
            self._prefill_start: Dict[tuple, int] = {}
            self._pending_copies: List[tuple] = []
        self.caches = symbiosis.init_client_caches(
            cfg, self.n_clients, max_batch_per_client, scfg.max_seq, **cache_kw)
        self._place_on_mesh()
        # one jitted masked-prefill per bank (admission runs the admitted
        # client's OWN method); the masked bank-wide decode exists only for
        # single-method engines (it vmaps one homogeneous adapter tree)
        self._prefill_one = [_jit_client_prefill(cfg, a, scfg, mesh)
                             for a in self.bank_cfgs]
        self._prefill_bank = (_jit_bank_prefill(cfg, acfg, scfg, mesh)
                              if bank_prefill else None)
        self._decode = (None if self._mixed
                        else _jit_masked_decode(cfg, acfg, scfg, mesh))
        # Compute-proportional decode (ISSUE 3 tentpole): gather the active
        # (client, slot) rows into one dense batch and run ONLY those —
        # FLOPs/HBM scale with active tokens, not bank size. Paged layouts
        # only (the page pools are what let the client axis fold away);
        # auto-enabled there, the masked bank-wide step stays as the
        # ablation (compact_decode=False) and the dense-layout path.
        if compact_decode and not self._paged:
            raise ValueError("compact_decode requires the paged KV layout "
                             "(ServeConfig.page_block > 0)")
        self._compact = self._paged if compact_decode is None else compact_decode
        # probe=True compiles the per-row finite reduction INTO the step
        # (docs/robustness.md): non-finite decode logits surface on the
        # host as a cheap [rows] bool without materializing [rows, V]
        self._compact_step = (_jit_compact_decode(
            cfg, self.bank_cfgs if self._mixed else acfg, scfg, mesh,
            probe=True)
            if self._compact else None)
        # jit-bucketed row-batch sizes: 4, 8, ... capped at the bank's rows
        total_rows = self.n_clients * self.max_b
        self._buckets = []
        b = 4
        while b < total_rows:
            self._buckets.append(b)
            b *= 2
        self._buckets.append(total_rows)
        # Ragged shared prefill (ROADMAP): several same-client admissions in
        # one tick batch into ONE masked prefill call with per-row lengths.
        # Right-padding to the longest prompt is exact for attention
        # families only; recurrent state (hybrid/RWKV) would be polluted by
        # pads, so those families keep one call per request.
        can_ragged = cfg.arch in (DENSE, MOE, VLM) and not bank_prefill
        if ragged_prefill and not can_ragged:
            raise ValueError("ragged_prefill right-pads rows to a shared "
                             "bucket; attention families only (and not the "
                             "bank_prefill ablation)")
        self._ragged = can_ragged if ragged_prefill is None else ragged_prefill
        # Cross-client compacted prefill (ISSUE 10 tentpole): on paged
        # attention engines the tick's admissions — ALL clients, ALL banks —
        # gather into ONE jit-bucketed ragged batch through
        # symbiosis.make_compact_prefill (the prefill analogue of the
        # compacted decode tick); the dense layout keeps the same-client
        # masked ragged path and recurrent families / ablations keep
        # per-request calls. Shared-prefix page reuse rides on top of the
        # compacted path: content-matched prompt-prefix pages are mapped at
        # admission (refcounted, read-only) and only the suffix prefills.
        # Sharing needs exact K/V bytes, so int8-quantized pools opt out.
        self._compact_prefill = self._ragged and self._paged
        can_share = self._compact_prefill and not self._quant
        if prefix_cache and not can_share:
            raise ValueError(
                "prefix_cache needs the compacted prefill path (paged "
                "attention-family engine, ragged_prefill not disabled) and "
                "an unquantized pool — int8 K/V doesn't round-trip "
                "(docs/prefix_cache.md)")
        self._share_prefix = can_share if prefix_cache is None else prefix_cache
        self._page_copy = (_jit_page_copy(cfg, scfg, mesh)
                           if self._share_prefix else None)
        # jit-key bookkeeping for the analysis bucket-coverage pass: the
        # epoch is bumped whenever admit_bank() legitimately changes hot-
        # path shapes, so post-growth compiles aren't read as recompiles
        self._trace_epoch = 0
        self._dead_clients: set = set()       # clients of retired banks
        # fault containment (docs/robustness.md): per-client health records,
        # the quarantine set (submit refuses; live requests terminated with
        # their resources freed through the normal retire path), an optional
        # deterministic fault hook for the chaos harness, and the per-tick
        # flag that keeps an injected admission fault from tripping the
        # "can never be admitted" stall detector
        self.health_policy = health_policy or HealthPolicy()
        self.debug = debug
        self.fault_hook = fault_hook
        self._client_health: Dict[int, HealthRecord] = {}
        self._quarantined_clients: set = set()
        self._admission_faulted = False
        self._queue: List[Request] = []
        # incremental service loop state: SymbiosisEngine interleaves
        # service_tick() with a FinetuneEngine's train ticks; run() is the
        # standalone drive-to-completion loop over the same method
        self._waiting: deque = deque()
        self._inflight: List[Request] = []
        self._done: List[Request] = []
        self._tick = 0
        # slot tables + per-request bookkeeping (keyed by id(req); requests
        # stay alive in the done list for the whole run)
        self._slot_owner = [[None] * self.max_b for _ in range(self.n_clients)]
        self._last_tok = np.zeros((self.n_clients, self.max_b), np.int32)
        # Incrementally maintained activity state (admit/retire only — never
        # re-derived from the request list inside the tick loop, whose cost
        # would grow with bank size): the bool mask drives the masked step,
        # the per-client sorted slot lists drive compacted row building.
        self._active_mask = np.zeros((self.n_clients, self.max_b), bool)
        self._active_slots: List[List[int]] = [[] for _ in range(self.n_clients)]
        self._left: Dict[int, int] = {}
        self._slots_of: Dict[int, List[int]] = {}
        self._rng: Dict[int, np.random.Generator] = {}
        self._placement: Dict[int, object] = {}
        # prefill_tokens counts LOGICAL prompt tokens admitted (layout- and
        # sharing-invariant); prefill_tokens_computed counts the tokens the
        # model actually ran — under shared-prefix hits only each row's
        # suffix — so the two diverge exactly by the reused prefix work
        self.stats = {"ticks": 0, "decode_tokens": 0, "prefill_tokens": 0,
                      "batched_clients": 0, "admitted": 0, "prefill_calls": 0,
                      "peak_inflight": 0, "compact_rows": 0, "compact_padded": 0,
                      "ragged_prefill_batches": 0, "faults": 0,
                      "quarantined_requests": 0, "rejected_requests": 0,
                      "quarantined_clients": 0, "compact_prefill_batches": 0,
                      "compact_prefill_rows": 0, "compact_prefill_padded": 0,
                      "prefill_tokens_computed": 0, "prefix_hits": 0,
                      "pages_shared": 0, "cow_copies": 0,
                      "decode_d2h_bytes": 0, "prefill_d2h_bytes": 0,
                      "page_ticks_held": 0, "page_ticks_reserved": 0,
                      "attn_pages_read": 0, "attn_pages_table": 0}
        # telemetry (docs/observability.md): obs=None is a hard no-op — the
        # tick loop sees only `is not None` guards plus the shared null
        # span; attached, all instrumentation is host-side (perf_counter at
        # tick/phase boundaries, no device syncs, no jit keys) and outputs
        # stay bitwise identical (tests/test_obs.py)
        self._obs = obs
        self._span = _null_span if obs is None else obs.span
        self._last_tok_t: Dict[int, float] = {}
        # per-client (decode token counter, inter-token histogram), looked
        # up once per client rather than once per row and tick
        self._decode_handles: Dict[int, tuple] = {}
        if obs is not None:
            obs.attach("serving", self)

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        assert 0 <= req.client_id < self.n_clients
        if req.client_id in self._dead_clients:
            raise ValueError(f"client {req.client_id} belongs to a retired "
                             "bank (see retire_bank)")
        if req.client_id in self._quarantined_clients:
            raise ValueError(f"client {req.client_id} is quarantined "
                             "(docs/robustness.md)")
        if req.prompt is None:
            # stream-backed prompt: shape checks happen at admission, when
            # the fetch resolves (and can fault — docs/robustness.md)
            if req.prompt_stream is None:
                raise ValueError("Request needs a prompt or a prompt_stream")
            assert req.max_new_tokens >= 1
        else:
            B, S = req.prompt.shape
            assert B <= self.max_b, f"request rows {B} > {self.max_b} slots"
            assert req.max_new_tokens >= 1
            assert S + req.max_new_tokens <= self.scfg.max_seq, (
                f"context {S}+{req.max_new_tokens} exceeds cache depth "
                f"{self.scfg.max_seq}")
        if req.sampling is not None and req.sampling.method not in (
                "greedy", "temperature", "top_k"):
            raise ValueError(f"unknown sampling method {req.sampling.method!r}")
        req.submit_t = time.perf_counter()
        self._queue.append(req)

    def pending(self) -> bool:
        """True while any request is queued, waiting, or in flight."""
        return bool(self._queue or self._waiting or self._inflight)

    @property
    def n_inflight(self) -> int:
        """Requests currently holding slots/pages/router capacity (what a
        co-scheduler checks before treating an admission stall as fatal)."""
        return len(self._inflight)

    def drain_done(self) -> List[Request]:
        """Hand over (and forget) the finished-request list. Each record
        carries its latency timeline (``queue_wait`` / ``ttft`` /
        ``e2e_latency`` properties) and ``fault_history``."""
        done, self._done = self._done, []
        return done

    def drain_events(self, *, client=None, kind: Optional[str] = None):
        """Client-visible event stream (docs/observability.md): drain THIS
        engine's telemetry events, optionally filtered to one client id
        and/or one event kind — filtered drains leave other tenants' (and,
        under a shared ``Obs``, the finetune engine's) events queued.
        Returns [] when the engine runs without telemetry (obs=None)."""
        if self._obs is None:
            return []
        if client is None:
            return self._obs.drain_events(kind=kind, engine="serving")
        return self._obs.drain_events(client=client, kind=kind,
                                      engine="serving")

    def service_tick(self) -> bool:
        """ONE engine tick: admission (+ the admitted requests' prefills),
        the policy-chosen decode tick, retirement. The incremental form of
        ``run()`` — ``SymbiosisEngine`` interleaves these with a
        FinetuneEngine's train steps against the same base. Returns True
        while requests remain."""
        obs = self._obs
        t0 = obs.tick_start("serving") if obs is not None else 0.0
        if self._queue:
            # merge new submissions (mid-run submits are allowed; order is
            # stable for equal arrive_ticks)
            self._waiting = deque(sorted(list(self._waiting) + self._queue,
                                         key=lambda r: r.arrive_tick))
            self._queue.clear()
        waiting, inflight = self._waiting, self._inflight
        if not waiting and not inflight:
            return False
        tick = self._tick
        self._admission_faulted = False
        # -- admission (continuous except under lockstep's batch barrier);
        # slots/pages/router capacity are claimed per request, then all of
        # this tick's admissions prefill together (ragged where possible)
        admitted_any = False
        newly = []
        with self._span("admit"):
            # the backoff gate (docs/robustness.md): a SUSPECT client's
            # requests skip admission until its deterministic backoff
            # expires — mirrored from the train-side job gate; backed-off
            # requests don't count as "attempted" for the stall detector
            # (backoff is bounded by HealthPolicy.max_backoff ticks)
            attempted, backing_off = [], 0
            for r in waiting:
                if r.arrive_tick > tick:
                    continue
                rec = self._client_health.get(r.client_id)
                if rec is not None and not rec.eligible(tick):
                    backing_off += 1
                    continue
                attempted.append(r)
            if self.policy.admit_now(len(inflight)):
                for req in attempted:
                    if req.client_id in self._quarantined_clients:
                        continue      # swept to rejected by _quarantine_client
                    if req.status == "rejected":
                        continue      # stream ran dry inside _try_admit
                    slots = self._try_admit(req)
                    if slots is not None:
                        waiting.remove(req)
                        inflight.append(req)
                        newly.append((req, slots))
                        admitted_any = True
        if obs is not None and backing_off:
            obs.metrics.counter("serve_backoff_skips_total").inc(backing_off)
        with self._span("prefill"):
            self._prefill_admitted(newly)

        self.stats["peak_inflight"] = max(self.stats["peak_inflight"],
                                          len(inflight))
        # -- decode tick over the policy-chosen subset of ready clients
        ready = sorted({r.client_id for r in inflight if self._left[id(r)] > 0})
        serve = self.policy.serving_set(ready)
        if serve:
            self._decode_tick(set(serve), inflight)

        # -- retire finished sequences; their slots free immediately
        for req in list(inflight):
            if self._left[id(req)] == 0:
                self._retire(req)
                inflight.remove(req)
                self._done.append(req)
        if serve and self._paged:
            self._count_pages()

        if (not inflight and attempted and not admitted_any and not serve
                and not self._admission_faulted):
            # nothing in flight to ever free capacity, and admission of
            # every due request just failed -> stuck forever (an injected
            # transient admission fault is NOT stuck: the retry may succeed)
            raise AdmissionStall(
                f"{len(attempted)} request(s) can never be admitted "
                f"(no free capacity and nothing in flight)")
        tick += 1
        if not inflight and waiting and all(r.arrive_tick > tick for r in waiting):
            tick = min(r.arrive_tick for r in waiting)           # idle skip
        self._tick = tick
        if self.debug:
            with self._span("health_audit"):
                from repro.faults.audit import serving_conservation
                errs = serving_conservation(self)
                assert not errs, "; ".join(errs)
        if obs is not None:
            obs.tick_end("serving", tick, t0)
        return bool(waiting or inflight)

    def run(self) -> List[Request]:
        """Serve all queued requests to completion; returns finished list."""
        while self.service_tick():
            pass
        return self.drain_done()

    # ------------------------------------------------------------------
    # admission + prefill
    # ------------------------------------------------------------------
    def _try_admit(self, req: Request) -> Optional[List[int]]:
        """Claim capacity for a request: slots, pages (under paging), and a
        router placement. Returns the claimed slot list (admitted — the
        caller prefills via ``_prefill_admitted``) or None (stays queued)."""
        c = req.client_id
        if req.prompt is None and not self._fetch_prompt(req):
            return None
        B, S = req.prompt.shape
        if self.max_inflight is not None:
            owners = {id(o) for o in self._slot_owner[c] if o is not None}
            if len(owners) >= self.max_inflight:
                return None
        free = [s for s in range(self.max_b) if self._slot_owner[c][s] is None]
        if len(free) < B:
            return None
        ctx_tokens = S + req.max_new_tokens
        hits = None
        if self._paged:
            # Reserve pages for the FULL context up front (deadlock freedom:
            # a running sequence can always draw its next page) but assign
            # them lazily — the block table only maps pages whose tokens
            # exist. Admission backpressure = not enough unreserved pages.
            pages_per_row = -(-ctx_tokens // self._blk)
            prompt_pages = -(-S // self._blk)
            need = pages_per_row * B
            if self._share_prefix:
                # shared-prefix lookup (read-only; refs are taken inside
                # the transactional block below): content-matched prefix
                # pages are mapped instead of popped, so backpressure and
                # the router charge count only NEWLY allocated pages
                scope = self._prefix_scope(c)
                hits = [self._prefix_index.lookup(scope, req.prompt[i],
                                                  self._blk)
                        for i in range(B)]
                need -= sum(h.matched_blocks for h in hits)
            if len(self._free_pages[c]) - self._reserved[c] < need:
                return None
        placement = None
        if self.router is not None:
            # charge what the layout pins: whole NEWLY-ALLOCATED pages under
            # paging (shared-prefix pages are already charged to their
            # publisher), a full max_seq-deep dense slot row otherwise
            alloc_tokens = (-(-need * self._blk // B) if self._paged
                            else self.scfg.max_seq)
            try:
                placement = self.router.route(ctx_tokens, B,
                                              latency_sensitive=req.latency_sensitive,
                                              alloc_tokens=alloc_tokens,
                                              quant=self._quant)
            except RuntimeError:
                return None                      # stays queued until capacity frees
        slots = free[:B]
        # TRANSACTIONAL from here on: the router charge is already committed
        # and the page pops below are multi-step — any failure mid-flight
        # must restore every structure exactly or the request leaks its
        # charge/pages forever (docs/robustness.md, admission-leak test)
        done_slots: List[int] = []
        tbl_rows = self._tbl[c, slots].copy() if self._paged else None
        wpos_rows = self._wpos[c, slots].copy() if self._paged else None
        n_copies0 = len(self._pending_copies) if self._paged else 0
        try:
            if self.fault_hook is not None:
                self.fault_hook("serve_admit", c)
            if self._paged:
                for i, s in enumerate(slots):
                    hit = hits[i] if hits is not None else None
                    shared: List[int] = []
                    pages: List[int] = []
                    # register BEFORE popping/reffing so a mid-flight
                    # failure still sees every page and reference taken so
                    # far in the rollback sweep
                    self._slot_shared[(c, s)] = shared
                    self._slot_pages[(c, s)] = pages
                    done_slots.append(s)
                    if hit is not None:
                        for d in hit.full_digests:
                            shared.append(self._prefix_index.ref(d))
                    for _ in range(prompt_pages - len(shared)):
                        pages.append(self._free_pages[c].pop())
                    self._tbl[c, s, :] = self._tbl_oob
                    self._tbl[c, s, :len(shared)] = shared
                    self._tbl[c, s, len(shared):prompt_pages] = pages
                    self._wpos[c, s] = S
                    start = 0
                    if hit is not None:
                        start = hit.start
                        if hit.tail_page is not None:
                            # CoW: the matched partial tail copies into this
                            # row's first exclusive page before the suffix
                            # prefill reads it (flushed in _prefill_compact)
                            self._pending_copies.append(
                                (hit.tail_page, pages[0]))
                    self._prefill_start[(c, s)] = start
                self._resv_of[id(req)] = (pages_per_row - prompt_pages) * B
                self._reserved[c] += self._resv_of[id(req)]
                self._tbl_dirty = True
        except BaseException as e:
            # pop() draws from the END of the free list, so extending with
            # each slot's pages reversed — newest slot first — restores the
            # pool's exact order (a retried admission then draws the SAME
            # pages, keeping the transient-recovery trajectory bitwise);
            # shared-prefix refs drop in the same reverse order (a ref taken
            # here can't be the last one — the publisher still holds its own)
            for s in reversed(done_slots):
                self._free_pages[c].extend(
                    reversed(self._slot_pages.pop((c, s))))
                for p in reversed(self._slot_shared.pop((c, s), [])):
                    if self._prefix_index.deref(p):
                        self._free_pages[p // self._pool_pages].append(p)
                self._prefill_start.pop((c, s), None)
            if self._paged:
                del self._pending_copies[n_copies0:]
                self._tbl[c, slots] = tbl_rows
                self._wpos[c, slots] = wpos_rows
                resv = self._resv_of.pop(id(req), None)
                if resv is not None:
                    self._reserved[c] -= resv
            if placement is not None:
                self.router.release(placement)
            if isinstance(e, TransientFault):
                self._fault_backoff(req, f"admission: {e}")
                return None                      # stays queued; retried next tick
            raise
        self._placement[id(req)] = placement
        for s in slots:
            self._slot_owner[c][s] = req
        req.admit_t = time.perf_counter()
        if hits is not None:
            n_hit = sum(1 for h in hits if h.start > 0)
            if n_hit:
                n_shared = sum(h.matched_blocks for h in hits)
                n_cow = sum(1 for h in hits if h.tail_page is not None)
                self.stats["prefix_hits"] += n_hit
                self.stats["pages_shared"] += n_shared
                self.stats["cow_copies"] += n_cow
                if self._obs is not None:
                    m = self._obs.metrics
                    m.counter("prefix_cache_hits_total", client=c).inc(n_hit)
                    m.counter("pages_shared", client=c).inc(n_shared)
                    if n_cow:
                        m.counter("cow_copies_total", client=c).inc(n_cow)
        if self._obs is not None:
            m = self._obs.metrics
            m.histogram("serve_queue_wait_seconds", client=c).observe(
                req.admit_t - req.submit_t)
            if self._paged:
                m.gauge("serve_pages_free", client=c).set(
                    len(self._free_pages[c]) - self._reserved[c])
            if placement is not None:
                m.counter("serve_hbm_charged_bytes_total", client=c).inc(
                    placement.cache_bytes)
            if self.router is not None:
                u = self.router.utilization()
                m.gauge("router_placements").set(u["placements"])
                m.gauge("router_committed_bytes").set(u["committed_bytes"])
            self._obs.event("admit", engine="serving", tick=self._tick,
                            tenant=c, rows=B, prompt_tokens=int(B * S))
            if req.fault_history:
                # a previously backed-off request made it through: the
                # client-visible signal that its retry succeeded
                self._obs.event("retry", engine="serving", tick=self._tick,
                                tenant=c, attempts=len(req.fault_history))
        return slots

    def _fault_backoff(self, req: Request, reason: str):
        """Shared transient-admission-fault path: health trip -> SUSPECT
        with deterministic backoff (event kind ``backoff``) or, past the
        retry budget, client quarantine. Admission state was already rolled
        back; the request stays queued for a bitwise retry."""
        c = req.client_id
        self._admission_faulted = True
        self.stats["faults"] += 1
        rec = self._client_health.setdefault(c, HealthRecord())
        verdict = rec.trip(self._tick, reason, self.health_policy)
        req.fault_history.append((self._tick, "backoff", reason))
        if self._obs is not None:
            self._obs.event("backoff", engine="serving", tick=self._tick,
                            tenant=c, reason=reason,
                            until=rec.next_eligible_tick)
        if verdict == "quarantine":
            self._quarantine_client(c)

    def _fetch_prompt(self, req: Request) -> bool:
        """Resolve a stream-backed request's prompt at admission time — the
        serving twin of the train-side ``FaultyStream`` injection point
        (docs/robustness.md). Runs BEFORE any admission state commits, so
        a delivery fault needs no rollback: transient errors back the
        client off (the retried fetch draws the same prompt — bitwise);
        exhaustion or an invalid prompt rejects the request. Returns True
        when ``req.prompt`` is resolved and valid."""
        c = req.client_id
        try:
            prompt = np.asarray(req.prompt_stream.fetch(), np.int32)
            if prompt.ndim != 2:
                raise ValueError(f"stream prompt must be [B, S], got "
                                 f"shape {prompt.shape}")
            B, S = prompt.shape
            if B > self.max_b or \
                    S + req.max_new_tokens > self.scfg.max_seq:
                raise ValueError(f"stream prompt [{B}, {S}] does not fit "
                                 f"({self.max_b} slots, depth "
                                 f"{self.scfg.max_seq})")
        except Exception as e:
            if classify(e) == "transient":
                self._fault_backoff(req, f"request stream: {e}")
            else:
                # stream ran dry / delivered garbage: reject this request
                # (and only it — the client stays healthy). Flagging
                # _admission_faulted keeps the removal from tripping the
                # same-tick stall detector.
                self._admission_faulted = True
                req.status = "rejected"
                req.fault_history.append(
                    (self._tick, "rejected", f"request stream: {e}"))
                self._waiting.remove(req)
                self._done.append(req)
                self.stats["rejected_requests"] += 1
                if self._obs is not None:
                    self._obs.event("reject", engine="serving",
                                    tick=self._tick, tenant=c,
                                    reason=f"request stream: {e}")
            return False
        req.prompt = prompt
        return True

    def _finish_admit(self, req: Request, slots: List[int],
                      first_logits: np.ndarray):
        """Post-prefill admission bookkeeping: sample the first token and
        activate the request's slots for decode ticks."""
        c = req.client_id
        B = req.prompt.shape[0]
        sp = req.sampling or SamplingParams()
        self._rng[id(req)] = np.random.default_rng([sp.seed, c])
        bad = ("client quarantined mid-tick"
               if c in self._quarantined_clients else
               "non-finite prefill logits"
               if not np.isfinite(first_logits).all() else None)
        if bad is not None:
            # non-finite prefill logits (poisoned adapter / corrupt weights)
            # quarantine the request before its first token ever samples —
            # left stays 0 so this tick's retire loop frees slots, pages and
            # the router charge through the one normal path
            req.generated = np.zeros((B, req.max_new_tokens), np.int32)
            req.status = "quarantined"
            req.fault_history.append((self._tick, "quarantine", bad))
            self._left[id(req)] = 0
            self._slots_of[id(req)] = slots
            self.stats["quarantined_requests"] += 1
            if self._obs is not None:
                self._obs.event("quarantine", engine="serving",
                                tick=self._tick, tenant=c, scope="request",
                                reason=bad)
            if bad == "non-finite prefill logits":
                self._fault_client(c, bad)
            return
        first = self._sample(first_logits, req)
        req.first_token_t = time.perf_counter()
        req.generated = np.zeros((B, req.max_new_tokens), np.int32)
        req.generated[:, 0] = first
        self._last_tok[c, slots] = first
        self._left[id(req)] = req.max_new_tokens - 1
        self._slots_of[id(req)] = slots
        if self._obs is not None:
            m = self._obs.metrics
            m.counter("serve_prefill_tokens_total", client=c).inc(
                int(req.prompt.size))
            m.histogram("serve_ttft_seconds", client=c).observe(
                req.first_token_t - req.submit_t)
            # the first decode token's inter-token gap measures from here
            self._last_tok_t[id(req)] = req.first_token_t
        if self._left[id(req)] > 0:
            # a request admitted with max_new_tokens == 1 is already done
            # (its one token came from prefill) and must never join a decode
            # tick: its slot's next block-table entry is still unassigned,
            # and decoding through it would write another client's page
            self._active_mask[c, slots] = True
            self._active_slots[c] = sorted(self._active_slots[c] + slots)
        self.stats["admitted"] += 1

    def _prefill_admitted(self, newly: List[tuple]):
        """Prefill this tick's admissions through ONE of three paths:

        * paged attention engines (the default): the CROSS-CLIENT compacted
          prefill — every admitted row this tick, across clients and banks,
          in one jit-bucketed dispatch (``_prefill_compact``), shared-prefix
          rows prefilling only their suffix;
        * dense-layout attention engines with ``ragged_prefill``: the
          same-client masked ragged batch (ISSUE 4) — the paged fold isn't
          available without page pools;
        * recurrent families, ``ragged_prefill=False`` and the
          ``bank_prefill`` ablation: one masked call per request.

        All three are byte-identical per row: rows are independent (per-row
        causal attention, length-bounded writes, disjoint slot masks) —
        asserted across paths in tests/test_serving_engine.py and
        tests/test_prefix_cache.py."""
        if not newly:
            return
        if not self._ragged:
            for req, slots in newly:
                logits = (self._prefill_request_bankwide(req, slots)
                          if self.bank_prefill
                          else self._prefill_request(req, slots))
                self._finish_admit(req, slots, logits)
            return
        if self._compact_prefill:
            self._prefill_compact(newly)
            return
        by_client: Dict[int, List[tuple]] = {}
        for req, slots in newly:
            by_client.setdefault(req.client_id, []).append((req, slots))
        for c, items in by_client.items():
            if len(items) == 1:
                req, slots = items[0]
                self._finish_admit(req, slots,
                                   self._prefill_request(req, slots))
                continue
            logits = self._prefill_ragged(c, items)
            for req, slots in items:
                self._finish_admit(req, slots, logits[slots])

    def _prefill_ragged(self, c: int, items: List[tuple]) -> np.ndarray:
        """One ragged masked prefill for several same-client admissions:
        rows are right-padded to the longest prompt's jit bucket and each
        row's true ``lengths`` entry drives its positions, causal mask,
        last-token logit gather and (under paging) pool-write bounds.
        Returns the full [max_b, V] logits block."""
        S_pad = self._bucket(max(req.prompt.shape[1] for req, _ in items))
        toks = np.zeros((self.max_b, S_pad), np.int32)
        lengths = np.zeros((self.max_b,), np.int32)
        mask = np.zeros((self.max_b,), bool)
        for req, slots in items:
            B, S = req.prompt.shape
            toks[slots, :S] = req.prompt
            lengths[slots] = S
            mask[slots] = True
            self.stats["prefill_tokens"] += B * S
        self._sync_tbl()
        m = int(self._method_of[c])
        with self._mesh_ctx():
            logits, self.caches = tracecount.dispatch(
                self, "prefill", (m, S_pad), self._prefill_one[m],
                self.base, self.banks[m], self.caches, np.int32(c),
                np.int32(self._local_of[c]),
                jnp.asarray(toks), jnp.asarray(lengths), jnp.asarray(mask))
        self.stats["prefill_calls"] += 1
        self.stats["ragged_prefill_batches"] += 1
        logits = np.asarray(logits)
        self.stats["prefill_d2h_bytes"] += logits.nbytes
        return logits

    def _prefill_compact(self, newly: List[tuple]):
        """ONE compacted prefill for the whole tick's admissions (ISSUE 10
        tentpole): gather every admitted (client, slot) row — cross-client,
        cross-bank — into a jit-bucketed ragged batch and scatter the
        results back under the row mask, the exact prefill analogue of
        ``_decode_tick_compact``. Each row carries the suffix start recorded
        at admission; rows with shared-prefix pages attend to their first
        ``ext_blocks`` block-table entries as read-only prefix lanes and
        prefill only their suffix. Queued CoW tail copies flush first, so
        every prefix page a row reads already holds its final bytes."""
        with self._span("prefill_compact_gather"):
            rows = []                        # (req, slot, row-in-request)
            for req, slots in newly:
                for i, s in enumerate(slots):
                    rows.append((req, s, i))
            n = len(rows)
            nb = self._row_bucket(n)
            starts = np.zeros((nb,), np.int32)
            suffix = np.zeros((n,), np.int32)
            for r, (req, s, i) in enumerate(rows):
                starts[r] = self._prefill_start.pop((req.client_id, s), 0)
                suffix[r] = req.prompt.shape[1] - starts[r]
            S_pad = self._bucket(int(suffix.max()))
            ext = self._ext_bucket(
                int(max(-(-int(starts[r]) // self._blk) for r in range(n))))
            toks = np.zeros((nb, S_pad), np.int32)
            lengths = np.zeros((nb,), np.int32)
            clients = np.zeros((nb,), np.int32)
            slot_ids = np.zeros((nb,), np.int32)
            rmask = np.zeros((nb,), bool)
            for r, (req, s, i) in enumerate(rows):
                toks[r, :suffix[r]] = req.prompt[i, starts[r]:]
                lengths[r] = suffix[r]
                clients[r] = req.client_id
                slot_ids[r] = s
                rmask[r] = True
                self.stats["prefill_tokens"] += int(req.prompt.shape[1])
                self.stats["prefill_tokens_computed"] += int(suffix[r])
        self._flush_page_copies()
        self._sync_tbl()
        fn = _jit_compact_prefill(
            self.cfg, self.bank_cfgs if self._mixed else self.bank_cfgs[0],
            self.scfg, self.mesh, ext)
        key = (nb, S_pad, ext)
        if self._mixed:
            with self._span("prefill_dispatch"), self._mesh_ctx():
                logits, finite, self.caches = tracecount.dispatch(
                    self, "compact_prefill", key, fn,
                    self.base, tuple(self.banks), self.caches,
                    jnp.asarray(toks), jnp.asarray(lengths),
                    jnp.asarray(starts), jnp.asarray(clients),
                    jnp.asarray(slot_ids),
                    jnp.asarray(self._method_of[clients]),
                    jnp.asarray(self._local_of[clients]),
                    jnp.asarray(rmask))
        else:
            with self._span("prefill_dispatch"), self._mesh_ctx():
                logits, finite, self.caches = tracecount.dispatch(
                    self, "compact_prefill", key, fn,
                    self.base, self.banks[0], self.caches,
                    jnp.asarray(toks), jnp.asarray(lengths),
                    jnp.asarray(starts), jnp.asarray(clients),
                    jnp.asarray(slot_ids), jnp.asarray(rmask))
        with self._span("prefill_sync"):
            logits = np.asarray(logits)
        self.stats["prefill_d2h_bytes"] += logits.nbytes
        self.stats["prefill_calls"] += 1
        self.stats["compact_prefill_batches"] += 1
        self.stats["compact_prefill_rows"] += n
        self.stats["compact_prefill_padded"] += nb - n
        if self._obs is not None:
            h = self._obs.metrics.histogram("admission_prefill_tokens")
            for L in suffix:
                h.observe(float(L))
        rows_of: Dict[int, List[int]] = {}
        for r, (req, s, i) in enumerate(rows):
            rows_of.setdefault(id(req), []).append(r)
        for req, slots in newly:
            self._finish_admit(req, slots, logits[rows_of[id(req)]])
            self._publish_prefix(req, slots)

    def _publish_prefix(self, req: Request, slots: List[int]):
        """Register a freshly prefilled request's prompt-prefix pages in the
        content index (docs/prefix_cache.md). Published full blocks move
        from the slot's exclusive list to its ref-held shared list (refs=1
        — the publisher's own reference); a partially-filled tail page
        stays exclusive but is indexed for copy-on-write hits. Duplicate
        digests (content already published) are skipped inside the index,
        so re-publishing a hit row only extends the chain with its new
        blocks."""
        if not self._share_prefix or req.status != "ok":
            return
        c = req.client_id
        scope = self._prefix_scope(c)
        for i, s in enumerate(slots):
            shared = self._slot_shared[(c, s)]
            pages = self._slot_pages[(c, s)]
            took = self._prefix_index.publish(
                scope, req.prompt[i], self._blk, shared + pages, (c, s))
            for p in took:      # block order is preserved on both lists
                pages.remove(p)
                shared.append(p)

    def _prefix_scope(self, c: int) -> bytes:
        """Digest scope for client ``c``'s prefix pages: the adapter
        identity. ANY adapter changes deeper layers' K/V — a layer-l delta
        shifts the residual stream feeding layer l+1's K/V projections —
        so pages are sharable only between prompts served by the same
        (bank, local adapter) pair, i.e. the same client or a client
        admitted over identical adapter rows."""
        return b"%d:%d" % (int(self._method_of[c]), int(self._local_of[c]))

    def _ext_bucket(self, e: int) -> int:
        """Jit-bucketed ext_blocks: 0 stays 0 (compiles the exact
        no-sharing program), otherwise the next power of two capped at the
        per-slot table depth."""
        if e <= 0:
            return 0
        b = 1
        while b < e:
            b *= 2
        return min(b, self._n_blocks)

    def _flush_page_copies(self):
        """Dispatch the admission-queued CoW page copies. One donated
        jitted program (src/dst are traced scalars) copies a single pool
        page across every layer's lanes; copies run before the compacted
        prefill so shared tails are in place when the suffix reads them."""
        if not self._pending_copies:
            return
        copies, self._pending_copies = self._pending_copies, []
        with self._mesh_ctx():
            for src, dst in copies:
                self.caches = tracecount.dispatch(
                    self, "page_copy", (), self._page_copy,
                    self.caches, jnp.int32(src), jnp.int32(dst))

    def _bucket(self, S: int) -> int:
        """Jit-bucketed prompt length. Attention families tolerate right-
        padding exactly (see model.prefill); recurrent families (hybrid,
        RWKV) must prefill at true length or pads pollute the state."""
        if self.cfg.arch not in (DENSE, MOE, VLM):
            return S
        b = 8
        while b < S:
            b *= 2
        return min(b, self.scfg.max_seq)

    def _mesh_ctx(self):
        """Ambient-mesh context for jitted dispatch: binds the engine mesh
        while tracing/running a step so the soft constraints inside the hot
        path (``common.constrain``) resolve; a no-op single-device."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def _place_on_mesh(self):
        """``device_put`` the engine's mutable state onto the mesh: caches
        (page pools by their page axis, per-slot leaves by the client axis)
        and each adapter bank (client axis; ``BankSpec.placement ==
        "replicated"`` keeps a bank whole on every device). Idempotent —
        re-run after ``admit_bank`` growth to place the appended state."""
        if self.mesh is None:
            return
        from repro.launch import shardings
        self._cache_specs = shardings.serving_cache_specs(
            self.cfg, self.scfg, self.mesh, self.caches)
        self.caches = shardings.put_tree(self.mesh, self.caches,
                                         self._cache_specs)
        for m, b in enumerate(self.banks):
            repl = m < len(self._bank_repl) and self._bank_repl[m]
            self.banks[m] = shardings.put_tree(
                self.mesh, b,
                shardings.bank_state_specs(self.cfg, self.mesh, b,
                                           replicated=repl))
        if not self._mixed:
            self.bank = self.banks[0]

    def _sync_tbl(self):
        """Push the block-table mirror to the device cache tree if the host
        allocator changed it since the last jitted call."""
        if self._paged and self._tbl_dirty:
            tbl = jnp.asarray(self._tbl)
            if self.mesh is not None:
                # commit to the table's canonical placement so the jitted
                # steps see ONE input-sharding signature whether the tick's
                # table came from the host mirror or the previous step
                from jax.sharding import NamedSharding
                tbl = jax.device_put(tbl, NamedSharding(
                    self.mesh, self._cache_specs["block_tbl"]))
            self.caches = dict(self.caches, block_tbl=tbl)
            self._tbl_dirty = False

    def _prefill_request(self, req: Request, slots: List[int]) -> np.ndarray:
        """Masked single-client prefill into the assigned slots.

        Returns the [B, V] logits of the prompt's last position per row."""
        c = req.client_id
        B, S = req.prompt.shape
        if self.bank_prefill:
            return self._prefill_request_bankwide(req, slots)
        S_pad = self._bucket(S)
        toks = np.zeros((self.max_b, S_pad), np.int32)
        toks[slots, :S] = req.prompt
        mask = np.zeros((self.max_b,), bool)
        mask[slots] = True
        # zero length on non-admitted rows: their logits/pos are discarded by
        # the slot-mask merge anyway, and under paging a zero length is what
        # keeps the masked prefill's scatter off other slots' live pages
        lengths = np.where(mask, S, 0).astype(np.int32)
        self._sync_tbl()
        m = int(self._method_of[c])
        with self._mesh_ctx():
            logits, self.caches = tracecount.dispatch(
                self, "prefill", (m, S_pad), self._prefill_one[m],
                self.base, self.banks[m], self.caches, np.int32(c),
                np.int32(self._local_of[c]),
                jnp.asarray(toks), jnp.asarray(lengths), jnp.asarray(mask))
        self.stats["prefill_calls"] += 1
        self.stats["prefill_tokens"] += B * S
        logits = np.asarray(logits)
        self.stats["prefill_d2h_bytes"] += logits.nbytes
        return logits[slots]

    def _prefill_request_bankwide(self, req: Request, slots: List[int]) -> np.ndarray:
        """Seed-engine ablation: pad the request into a bank-wide [C, max_b,
        S] prefill (C× the base compute of the masked path) and replace the
        whole client cache slice."""
        c = req.client_id
        B, S = req.prompt.shape
        toks = np.zeros((self.n_clients, self.max_b, S), np.int32)
        toks[c, slots] = req.prompt
        with self._mesh_ctx():
            logits, new_caches = tracecount.dispatch(
                self, "bank_prefill", (S,), self._prefill_bank,
                self.base, self.bank, self.caches, {"tokens": jnp.asarray(toks)})
        sel = np.zeros((self.n_clients,), bool)
        sel[c] = True
        sel = jnp.asarray(sel)

        def merge(old, new):
            return jnp.where(sel.reshape((self.n_clients,) + (1,) * (old.ndim - 1)),
                             new, old)

        self.caches = jax.tree.map(merge, self.caches, new_caches)
        self.stats["prefill_calls"] += 1
        self.stats["prefill_tokens"] += B * S
        logits = np.asarray(logits)
        self.stats["prefill_d2h_bytes"] += logits.nbytes
        return logits[c, slots]

    # ------------------------------------------------------------------
    # decode + sampling
    # ------------------------------------------------------------------
    def _grow_slot_pages(self, req: Request, c: int, s: int):
        """Assign the next page when this tick's token write crosses a block
        boundary (reservation guarantees the pool can serve it)."""
        w = int(self._wpos[c, s])
        bi = w // self._blk
        pages = self._slot_pages[(c, s)]
        # coverage = ref-held shared prefix pages (block-table front) plus
        # exclusive pages; growth pages are always exclusive — a decode
        # write never lands on a shared page (its block is already full)
        covered = len(self._slot_shared.get((c, s), ())) + len(pages)
        if bi >= covered:
            page = self._free_pages[c].pop()
            pages.append(page)
            self._tbl[c, s, bi] = page
            self._reserved[c] -= 1
            self._resv_of[id(req)] -= 1
            self._tbl_dirty = True
        self._wpos[c, s] = w + 1

    def _row_bucket(self, n: int) -> int:
        """Smallest jit bucket holding n active rows (bounds recompiles)."""
        for b in self._buckets:
            if b >= n:
                return b
        return self._buckets[-1]

    def _decode_tick(self, serve: set, inflight: List[Request]):
        stepping = [r for r in inflight
                    if r.client_id in serve and self._left[id(r)] > 0]
        with self._span("compact_gather"):
            for req in stepping:
                if self._paged:
                    for s in self._slots_of[id(req)]:
                        self._grow_slot_pages(req, req.client_id, s)
            self._sync_tbl()
        if self._compact:
            lookup, finite_of = self._decode_tick_compact(serve)
        else:
            # masked bank-wide step: compose this tick's mask from the
            # incrementally maintained activity mask (admit/retire updates)
            # and the policy's serving set — O(C) per tick, not O(inflight)
            serve_sel = np.zeros((self.n_clients, 1), bool)
            serve_sel[sorted(serve)] = True
            active = self._active_mask & serve_sel
            with self._span("jit_dispatch"), self._mesh_ctx():
                logits, self.caches = tracecount.dispatch(
                    self, "decode", (), self._decode,
                    self.base, self.bank, self.caches,
                    jnp.asarray(self._last_tok), jnp.asarray(active))
            with self._span("device_sync"):
                lg = np.asarray(logits)
            self.stats["decode_d2h_bytes"] += lg.nbytes
            lookup = lambda c, slots: lg[c, slots]
            finite_of = lambda c, slots: bool(np.isfinite(lg[c, slots]).all())
        obs = self._obs
        with self._span("sample"):
            # ONE host timestamp after the decode's logits landed: every
            # stepping request's inter-token sample this tick shares it
            # (tick-boundary granularity, no per-request syncs)
            t_now = time.perf_counter() if obs is not None else 0.0
            # every stepping row's next token first (None: non-finite
            # logits, quarantined below), the bookkeeping after
            sampled = []
            for req in stepping:
                c, slots = req.client_id, self._slots_of[id(req)]
                sampled.append((req, self._sample(lookup(c, slots), req)
                                if finite_of(c, slots) else None))
        with self._span("scatter"):
            for req, nxt in sampled:
                if self._left[id(req)] <= 0:
                    continue          # its client was quarantined mid-tick
                if nxt is None:
                    self._quarantine_request(req, "non-finite decode logits")
                    continue
                c, slots = req.client_id, self._slots_of[id(req)]
                pos = req.max_new_tokens - self._left[id(req)]
                req.generated[:, pos] = nxt
                self._last_tok[c, slots] = nxt
                self._left[id(req)] -= 1
                self.stats["decode_tokens"] += len(slots)
                if obs is not None:
                    tokens, gaps = self._decode_handle(c)
                    tokens.inc(len(slots))
                    last = self._last_tok_t.get(id(req))
                    if last is not None:
                        gaps.observe(t_now - last)
                    self._last_tok_t[id(req)] = t_now
        self.stats["ticks"] += 1
        self.stats["batched_clients"] += len(serve)

    def _decode_handle(self, c: int) -> tuple:
        """Client ``c``'s decode-token counter and inter-token histogram."""
        h = self._decode_handles.get(c)
        if h is None:
            m = self._obs.metrics
            h = self._decode_handles[c] = (
                m.counter("serve_decode_tokens_total", client=c),
                m.histogram("serve_intertoken_seconds", client=c))
        return h

    def _count_pages(self):
        """Page-ticks of a decode tick: pages held by requests (taken from
        the pool) and pages promised to requests in flight but not yet
        taken. Their ratio over a window is the share of what admission
        claims that holds no token yet."""
        free = sum(len(f) for f in self._free_pages)
        self.stats["page_ticks_held"] += \
            self.n_clients * self._pool_pages - free
        self.stats["page_ticks_reserved"] += sum(self._reserved)

    def _decode_tick_compact(self, serve: set):
        """Compute-proportional tick: gather the serving clients' active
        (client, slot) rows into a bucketed dense batch, decode only those
        rows, and return a logits lookup for the sampler. The jitted step
        scatters cache writes back under the row mask (symbiosis.
        make_compact_decode_step); outputs are byte-identical to the masked
        bank-wide step — the bucket's padding rows are masked out of every
        write and their logits never read. The step is compiled with
        ``probe=True``, so a per-row finite flag rides along for free;
        returns ``(logits lookup, finite lookup)`` for the sampler."""
        with self._span("compact_gather"):
            rows = [(c, s) for c in sorted(serve)
                    for s in self._active_slots[c]]
            n = len(rows)
            nb = self._row_bucket(n)
            clients = np.zeros((nb,), np.int32)
            slots = np.zeros((nb,), np.int32)
            mask = np.zeros((nb,), bool)
            for i, (c, s) in enumerate(rows):
                clients[i], slots[i], mask[i] = c, s, True
            toks = self._last_tok[clients, slots]
        if self._mixed:
            # per-row method ids + bank-local adapter indices: one tick
            # carries every bank's rows through the mixed compact step
            with self._span("jit_dispatch"), self._mesh_ctx():
                logits, finite, self.caches = tracecount.dispatch(
                    self, "compact_decode", nb, self._compact_step,
                    self.base, tuple(self.banks), self.caches,
                    jnp.asarray(toks),
                    jnp.asarray(clients), jnp.asarray(slots),
                    jnp.asarray(self._method_of[clients]),
                    jnp.asarray(self._local_of[clients]), jnp.asarray(mask))
        else:
            with self._span("jit_dispatch"), self._mesh_ctx():
                logits, finite, self.caches = tracecount.dispatch(
                    self, "compact_decode", nb, self._compact_step,
                    self.base, self.bank, self.caches, jnp.asarray(toks),
                    jnp.asarray(clients), jnp.asarray(slots),
                    jnp.asarray(mask))
        with self._span("device_sync"):
            lg = np.asarray(logits)
            fin = np.asarray(finite)
        self.stats["decode_d2h_bytes"] += lg.nbytes + fin.nbytes
        row_of = {cs: i for i, cs in enumerate(rows)}
        self.stats["compact_rows"] += n
        self.stats["compact_padded"] += nb - n
        # the pages of the compute blocks paged attention visits for each
        # row (this tick's token sits at wpos - 1; a padding row reads
        # none), against the bucket's whole tables
        self.stats["attn_pages_read"] += pages_read(
            [self._wpos[c, s] - 1 for c, s in rows], self._n_blocks,
            self._blk, self.cfg.sliding_window)
        self.stats["attn_pages_table"] += nb * self._n_blocks
        return (lambda c, ss: lg[[row_of[(c, s)] for s in ss]],
                lambda c, ss: bool(fin[[row_of[(c, s)] for s in ss]].all()))

    def _sample(self, logits: np.ndarray, req: Request) -> np.ndarray:
        """logits [rows, V] -> next token per row, via the request's RNG."""
        sp = req.sampling
        if sp is None or sp.method == "greedy":
            return np.argmax(logits, axis=-1).astype(np.int32)
        if sp.method not in ("temperature", "top_k"):
            raise ValueError(f"unknown sampling method {sp.method!r}")
        z = logits.astype(np.float64) / max(sp.temperature, 1e-6)
        k = min(sp.top_k, z.shape[-1])          # top_k > vocab = no truncation
        if sp.method == "top_k" and k > 0:
            kth = np.partition(z, -k, axis=-1)[:, -k][:, None]
            z = np.where(z < kth, -np.inf, z)
        z = z - z.max(axis=-1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(axis=-1, keepdims=True)
        rng = self._rng[id(req)]
        return np.array([rng.choice(p.shape[-1], p=row) for row in p], np.int32)

    # ------------------------------------------------------------------
    # fault containment (docs/robustness.md)
    # ------------------------------------------------------------------
    def _quarantine_request(self, req: Request, reason: str):
        """Terminate a faulty in-flight request: remaining budget zeroed so
        this tick's retire loop frees its slots, pages and router charge
        through the one normal path. Repeated faults quarantine the client."""
        req.status = "quarantined"
        req.fault_history.append((self._tick, "quarantine", reason))
        self._left[id(req)] = 0
        self.stats["quarantined_requests"] += 1
        if self._obs is not None:
            self._obs.event("quarantine", engine="serving", tick=self._tick,
                            tenant=req.client_id, scope="request",
                            reason=reason)
        self._fault_client(req.client_id, reason)

    def _fault_client(self, c: int, reason: str):
        """Record a fault against a client; quarantine the whole client once
        ``HealthPolicy.client_quarantine_after`` faults accumulate."""
        self.stats["faults"] += 1
        rec = self._client_health.setdefault(c, HealthRecord())
        rec.total_faults += 1
        if rec.state is not HealthState.QUARANTINED:
            rec.state = HealthState.SUSPECT
            rec.history.append((self._tick, "suspect", reason))
            if self._obs is not None:
                self._obs.event("health", engine="serving", tick=self._tick,
                                tenant=c, state="suspect", reason=reason)
        if (c not in self._quarantined_clients and rec.total_faults
                >= self.health_policy.client_quarantine_after):
            self._quarantine_client(c)

    def _quarantine_client(self, c: int):
        """Fence a client off: refuse new submits, reject its queued/waiting
        requests, and terminate its in-flight ones (resources free through
        the normal retire path). Other clients' state is untouched — their
        streams stay bitwise identical to a run without the faulty tenant."""
        if c in self._quarantined_clients:
            return
        self._quarantined_clients.add(c)
        self.stats["quarantined_clients"] += 1
        rec = self._client_health.setdefault(c, HealthRecord())
        if rec.state is not HealthState.QUARANTINED:
            rec.state = HealthState.QUARANTINED
            rec.history.append((self._tick, "quarantined",
                                f"{rec.total_faults} fault(s)"))
        if self._obs is not None:
            self._obs.event("quarantine", engine="serving", tick=self._tick,
                            tenant=c, scope="client",
                            faults=rec.total_faults)
        for pool in (self._queue, self._waiting):
            for r in [r for r in pool if r.client_id == c]:
                pool.remove(r)
                r.status = "rejected"
                r.fault_history.append(
                    (self._tick, "rejected", "client quarantined"))
                self._done.append(r)
                self.stats["rejected_requests"] += 1
                if self._obs is not None:
                    self._obs.event("reject", engine="serving",
                                    tick=self._tick, tenant=c,
                                    reason="client quarantined")
        for r in self._inflight:
            if r.client_id == c and self._left.get(id(r), 0) > 0:
                r.status = "quarantined"
                r.fault_history.append(
                    (self._tick, "quarantine", "client quarantined"))
                self._left[id(r)] = 0
                self.stats["quarantined_requests"] += 1

    def _retire(self, req: Request):
        req.finish_t = time.perf_counter()
        c = req.client_id
        for s in self._slots_of.pop(id(req)):
            self._slot_owner[c][s] = None
            if self._active_mask[c, s]:       # never set for max_new == 1
                self._active_mask[c, s] = False
                self._active_slots[c].remove(s)
            if self._paged:
                # pages (and any unused reservation) return to the pool for
                # the next admit; the table rows are remapped at admission,
                # so stale entries can never be read through. Shared-prefix
                # pages RELEASE A REFERENCE instead of freeing: the page
                # recycles only when the last holder retires, and the
                # slot's tail-page index entries die with it (the tail page
                # itself is exclusive and frees normally)
                self._free_pages[c].extend(self._slot_pages.pop((c, s)))
                if self._share_prefix:
                    self._prefix_index.drop_tail((c, s))
                    for p in self._slot_shared.pop((c, s), []):
                        if self._prefix_index.deref(p):
                            self._free_pages[p // self._pool_pages].append(p)
                else:
                    self._slot_shared.pop((c, s), None)
                self._prefill_start.pop((c, s), None)
                self._wpos[c, s] = 0
        if self._paged:
            self._reserved[c] -= self._resv_of.pop(id(req), 0)
        del self._left[id(req)]
        self._rng.pop(id(req), None)
        placement = self._placement.pop(id(req), None)
        if placement is not None:
            self.router.release(placement)
        if self._obs is not None:
            self._last_tok_t.pop(id(req), None)
            m = self._obs.metrics
            m.histogram("serve_e2e_seconds", client=c).observe(
                req.finish_t - req.submit_t)
            if self._paged:
                m.gauge("serve_pages_free", client=c).set(
                    len(self._free_pages[c]) - self._reserved[c])
            if self.router is not None:
                u = self.router.utilization()
                m.gauge("router_placements").set(u["placements"])
                m.gauge("router_committed_bytes").set(u["committed_bytes"])
            self._obs.event(
                "retire", engine="serving", tick=self._tick, tenant=c,
                status=req.status,
                tokens=(0 if req.generated is None
                        else int(req.generated.size)))

    def release_banks(self):
        """Release the per-bank adapter-HBM charges committed at
        construction (mixed-method engines with a router attached)."""
        for p in self._bank_placements:
            self.router.release(p)
        self._bank_placements = []

    # ------------------------------------------------------------------
    # engine-level crash recovery (docs/robustness.md)
    # ------------------------------------------------------------------
    def _req_record(self, req: Request) -> dict:
        sp = req.sampling
        return {"client_id": req.client_id,
                "prompt": (None if req.prompt is None
                           else np.asarray(req.prompt)),
                "prompt_stream": req.prompt_stream,   # picklable by contract
                "max_new_tokens": req.max_new_tokens,
                "latency_sensitive": req.latency_sensitive,
                "sampling": None if sp is None else dataclasses.asdict(sp),
                "arrive_tick": req.arrive_tick,
                "generated": (None if req.generated is None
                              else np.asarray(req.generated)),
                "status": req.status,
                "fault_history": list(req.fault_history),
                "left": self._left.get(id(req)),
                "slots": self._slots_of.get(id(req)),
                "resv": self._resv_of.get(id(req)) if self._paged else None,
                "rng": (self._rng[id(req)].bit_generator.state
                        if id(req) in self._rng else None),
                "placed": id(req) in self._placement,
                "placement": self._placement.get(id(req))}

    def engine_state(self) -> dict:
        """Whole-engine host+device snapshot for crash recovery: every
        request (with its per-request RNG cursor, slot list, reservation
        and router placement), the page allocator, caches/banks as numpy,
        health records and stats. Restoring into a FRESHLY constructed
        identical engine (``load_engine_state``) resumes every tenant
        bitwise — asserted by the kill/restore tests. Single-device only;
        dynamically admitted banks (``admit_bank``) are not captured."""
        if self.mesh is not None:
            raise NotImplementedError("engine_state: single-device engines "
                                      "only (mesh=None)")
        state = {
            "inflight": [self._req_record(r) for r in self._inflight],
            "waiting": [self._req_record(r) for r in self._waiting],
            "queue": [self._req_record(r) for r in self._queue],
            "done": [self._req_record(r) for r in self._done],
            "caches": jax.tree.map(np.asarray, jax.device_get(self.caches)),
            "banks": [jax.tree.map(np.asarray, jax.device_get(b))
                      for b in self.banks],
            "last_tok": self._last_tok.copy(),
            "tick": self._tick,
            "stats": dict(self.stats),
            "client_health": dict(self._client_health),
            "quarantined_clients": set(self._quarantined_clients),
            "dead_clients": set(self._dead_clients),
        }
        if self._paged:
            state["alloc"] = {
                "free_pages": [list(x) for x in self._free_pages],
                "reserved": list(self._reserved),
                "wpos": self._wpos.copy(),
                "tbl": self._tbl.copy(),
                "slot_pages": {k: list(v)
                               for k, v in self._slot_pages.items()},
                "slot_shared": {k: list(v)
                                for k, v in self._slot_shared.items()},
                "prefix_index": self._prefix_index.state(),
            }
        return state

    def load_engine_state(self, state: dict):
        """Inverse of ``engine_state`` into a freshly constructed engine
        (same spec/base/banks/router capacities as the original — router
        placements are RE-COMMITTED here, so pass a fresh router, not the
        crashed engine's live one)."""
        if self.mesh is not None:
            raise NotImplementedError("load_engine_state: single-device "
                                      "engines only (mesh=None)")
        if self._inflight or self._waiting or self._queue or self._done:
            raise RuntimeError("load_engine_state needs a freshly "
                               "constructed engine")
        if len(state["banks"]) != len(self.banks):
            raise RuntimeError(f"checkpoint holds {len(state['banks'])} "
                               f"banks, engine has {len(self.banks)} "
                               "(admit_bank growth is not captured)")

        def mk(rec: dict) -> Request:
            sp = rec["sampling"]
            req = Request(client_id=rec["client_id"], prompt=rec["prompt"],
                          max_new_tokens=rec["max_new_tokens"],
                          latency_sensitive=rec["latency_sensitive"],
                          sampling=(None if sp is None
                                    else SamplingParams(**sp)),
                          arrive_tick=rec["arrive_tick"],
                          prompt_stream=rec.get("prompt_stream"))
            req.generated = rec["generated"]
            req.status = rec["status"]
            req.fault_history = list(rec.get("fault_history", []))
            if rec["left"] is not None:
                self._left[id(req)] = rec["left"]
            if rec["slots"] is not None:
                slots = list(rec["slots"])
                c = req.client_id
                self._slots_of[id(req)] = slots
                for s in slots:
                    self._slot_owner[c][s] = req
                if rec["left"]:
                    self._active_mask[c, slots] = True
                    self._active_slots[c] = sorted(self._active_slots[c]
                                                   + slots)
            if rec["rng"] is not None:
                rng = np.random.default_rng()
                rng.bit_generator.state = rec["rng"]
                self._rng[id(req)] = rng
            if self._paged and rec["resv"] is not None:
                self._resv_of[id(req)] = rec["resv"]
            if rec["placed"]:
                p = rec["placement"]
                self._placement[id(req)] = p
                if p is not None and self.router is not None:
                    self.router.commit(p)
            return req

        self._inflight = [mk(r) for r in state["inflight"]]
        self._waiting = deque(mk(r) for r in state["waiting"])
        self._queue = [mk(r) for r in state["queue"]]
        self._done = [mk(r) for r in state["done"]]
        self.caches = jax.tree.map(jnp.asarray, state["caches"])
        self.banks = [jax.tree.map(jnp.asarray, b) for b in state["banks"]]
        if not self._mixed:
            self.bank = self.banks[0]
        self._last_tok = state["last_tok"].copy()
        self._tick = state["tick"]
        self.stats.update(state["stats"])
        self._client_health = dict(state["client_health"])
        self._quarantined_clients = set(state["quarantined_clients"])
        self._dead_clients = set(state["dead_clients"])
        if self._paged:
            a = state["alloc"]
            self._free_pages = [list(x) for x in a["free_pages"]]
            self._reserved = list(a["reserved"])
            self._wpos = a["wpos"].copy()
            self._tbl = a["tbl"].copy()
            self._slot_pages = {tuple(k): list(v)
                                for k, v in a["slot_pages"].items()}
            self._slot_shared = {tuple(k): list(v)
                                 for k, v in a.get("slot_shared", {}).items()}
            self._prefix_index = PrefixIndex.from_state(
                a.get("prefix_index", {}))
            self._tbl_dirty = True      # re-push the restored table mirror

    # ------------------------------------------------------------------
    # dynamic bank admission (ROADMAP carry-over: the registry is no
    # longer fixed at construction)
    # ------------------------------------------------------------------
    def admit_bank(self, acfg, client_bank) -> BankAdmission:
        """Admit a bank of clients while the engine is live.

        ``acfg`` matching an existing bank GROWS that bank's client axis;
        a new ``acfg`` registers a new bank (a single-method engine grows
        into the mixed registry: the masked bank-wide decode can't carry
        two methods, so the compacted per-row-method tick becomes the only
        decode path). New clients take the global ids after the current
        ones; the global flat pool appends exactly their page ranges, so
        ``[c*P, (c+1)*P)`` stays the ownership rule and no existing page
        id, table entry or in-flight request moves. The attached router is
        charged the bank's resident adapter bytes HERE (``route_bank``) —
        admission backpressure happens before any state grows — and the
        charge is released by ``retire_bank``. Requires the paged layout +
        compacted decode. The jit keys this creates (grown row buckets,
        the new bank's prefill) are re-declared through ``trace_domain()``
        and a new ``_trace_epoch``, so the analysis bucket-coverage pass
        treats post-growth compiles as legal."""
        if not (self._paged and self._compact):
            raise ValueError("dynamic bank admission requires the paged KV "
                             "layout + compacted decode")
        if self.bank_prefill:
            raise ValueError("bank_prefill is a fixed-registry ablation")
        k = jax.tree.leaves(client_bank)[0].shape[0]
        placement = None
        if self.router is not None:
            _, nbytes = adapters_lib.adapter_bytes(self.cfg, acfg)
            placement = self.router.route_bank(nbytes * k)  # raises: no fit
        old_C = self.n_clients
        if acfg in self.bank_cfgs:
            m = self.bank_cfgs.index(acfg)
            old_local = jax.tree.leaves(self.banks[m])[0].shape[0]
            self.banks[m] = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b.astype(a.dtype)]),
                self.banks[m], client_bank)
            if not self._mixed:
                self.bank = self.banks[m]
            locs = np.arange(old_local, old_local + k, dtype=np.int32)
        else:
            if not self._mixed:
                self._mixed = True
                self._decode = None
                self.bank = None
            m = len(self.banks)
            self.bank_cfgs = self.bank_cfgs + (acfg,)
            self.banks.append(client_bank)
            self._bank_repl = self._bank_repl + (False,)
            self._prefill_one.append(
                _jit_client_prefill(self.cfg, acfg, self.scfg, self.mesh))
            locs = np.arange(k, dtype=np.int32)
        if self._mixed:
            self._compact_step = _jit_compact_decode(
                self.cfg, self.bank_cfgs, self.scfg, self.mesh, probe=True)
        self._method_of = np.concatenate(
            [self._method_of, np.full((k,), m, np.int32)])
        self._local_of = np.concatenate([self._local_of, locs])
        self.n_clients = old_C + k

        # grow the device caches: per-client leaves concat along the leading
        # client axis, pool leaves along the global page axis — the appended
        # pages ARE the new clients' ranges
        cache_kw = symbiosis.serve_cache_kwargs(self.cfg, self.scfg)
        cache_kw["pool_pages"] = self._pool_pages
        fresh = symbiosis.init_client_caches(
            self.cfg, k, self.max_b, self.scfg.max_seq, **cache_kw)
        page_axes = symbiosis.cache_page_axes(
            self.cfg, self.scfg.max_seq, **cache_kw)
        self.caches = jax.tree.map(
            lambda old, new, pax: jnp.concatenate(
                [old, new.astype(old.dtype)], axis=0 if pax is None else pax),
            self.caches, fresh, page_axes)

        # allocator + slot bookkeeping for the new clients
        self._free_pages.extend(
            [list(range(c * self._pool_pages, (c + 1) * self._pool_pages))
             for c in range(old_C, self.n_clients)])
        self._reserved.extend([0] * k)
        self._wpos = np.concatenate(
            [self._wpos, np.zeros((k, self.max_b), np.int64)])
        self._tbl = np.concatenate(
            [self._tbl, np.full((k, self.max_b, self._n_blocks),
                                self._tbl_oob, np.int32)])
        self._tbl_dirty = True
        self._slot_owner.extend([[None] * self.max_b for _ in range(k)])
        self._last_tok = np.concatenate(
            [self._last_tok, np.zeros((k, self.max_b), np.int32)])
        self._active_mask = np.concatenate(
            [self._active_mask, np.zeros((k, self.max_b), bool)])
        self._active_slots.extend([[] for _ in range(k)])

        total_rows = self.n_clients * self.max_b
        self._buckets = []
        b = 4
        while b < total_rows:
            self._buckets.append(b)
            b *= 2
        self._buckets.append(total_rows)
        self._place_on_mesh()       # grown caches + banks take their specs
        self._trace_epoch += 1
        if self._obs is not None:
            self._obs.event("bank_growth", engine="serving", tick=self._tick,
                            bank=m, clients=k, method=acfg.method)
        return BankAdmission(bank_id=m,
                             client_ids=list(range(old_C, self.n_clients)),
                             placement=placement)

    def retire_bank(self, admission: BankAdmission):
        """Retire a dynamically admitted bank: its clients stop accepting
        requests and the ``route_bank`` charge taken at ``admit_bank`` is
        released. Clients must be idle (nothing in flight). Their adapter
        rows, cache slots and pages stay allocated as dead capacity — global
        ids never move, so live clients are untouched."""
        busy = [c for c in admission.client_ids
                if any(o is not None for o in self._slot_owner[c])]
        if busy:
            raise RuntimeError(
                f"bank clients {busy} still have requests in flight")
        self._dead_clients.update(admission.client_ids)
        if admission.placement is not None:
            self.router.release(admission.placement)
            admission.placement = None
        if self._obs is not None:
            self._obs.event("bank_retire", engine="serving", tick=self._tick,
                            bank=admission.bank_id,
                            clients=len(admission.client_ids))

    # ------------------------------------------------------------------
    def trace_domain(self) -> tracecount.TraceDomain:
        """The closed set of legal jit cache keys (analysis 'buckets' pass).

        Computed live so ``admit_bank`` growth re-declares itself: prefill
        compiles (bank, prompt-bucket) pairs — a closed power-of-two set
        for attention families, unbounded for recurrent families which
        prefill at true length by design; the masked decode has one shape;
        compact decode compiles exactly the row buckets; the
        ``bank_prefill`` seed ablation is declared unbounded."""
        d = tracecount.TraceDomain()
        if self.cfg.arch in (DENSE, MOE, VLM):
            sbuckets = set()
            b = 8
            while True:
                sbuckets.add(min(b, self.scfg.max_seq))
                if b >= self.scfg.max_seq:
                    break
                b *= 2
            d.declare("prefill", {(m, s) for m in range(len(self.bank_cfgs))
                                  for s in sbuckets})
        else:
            d.declare("prefill", unbounded=True)
        if self._prefill_bank is not None:
            d.declare("bank_prefill", unbounded=True)
        if self._decode is not None:
            d.declare("decode", {()})
        if self._compact_step is not None:
            d.declare("compact_decode", set(self._buckets))
        if self._compact_prefill:
            # the compacted cross-client prefill compiles (row bucket,
            # suffix bucket, ext bucket) triples — every axis a closed set.
            # ext buckets beyond 0 exist only with shared-prefix reuse on.
            sbuckets = set()
            b = 8
            while True:
                sbuckets.add(min(b, self.scfg.max_seq))
                if b >= self.scfg.max_seq:
                    break
                b *= 2
            ebuckets = {0}
            if self._share_prefix:
                e = 1
                while e < self._n_blocks:
                    ebuckets.add(e)
                    e *= 2
                ebuckets.add(self._n_blocks)
            d.declare("compact_prefill", {(nb, s, e) for nb in self._buckets
                                          for s in sbuckets for e in ebuckets})
            if self._share_prefix:
                d.declare("page_copy", {()})
        return d

    # ------------------------------------------------------------------
    def simulate_policy(self, requests: List[Request], *, policy: str = None,
                        exec_overhead: float = 1e-4, per_token_cost: float = 1e-6,
                        client_side_time: float = 5e-5):
        """Scheduler-simulated timeline for these requests under a policy
        (Tables 4/5 reproduction; real outputs are policy-invariant)."""
        policy = policy or self.policy.name
        clients = [ClientSpec(client_id=r.client_id,
                              n_tokens=int(r.prompt.shape[0]),
                              client_side_time=client_side_time,
                              n_iterations=r.max_new_tokens,
                              latency_sensitive=r.latency_sensitive)
                   for r in requests]
        return simulate(clients, self.cfg.n_layers, policy,
                        exec_overhead, per_token_cost,
                        wait_fraction=self.scfg.wait_fraction)

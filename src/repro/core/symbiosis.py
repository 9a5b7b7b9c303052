"""Symbiosis system composition — the paper's contribution as a composable
JAX module.

Builds the multi-client steps in which ONE frozen base-parameter tree serves
a *bank* of clients (fine-tuning trainers and/or inference sessions):

* ``make_multi_client_train_step`` — C clients fine-tune their own adapters
  against the shared base. Client-side state (adapter params, AdamW state,
  per-client batch) carries a leading client axis (vmapped); base matmuls see
  the merged token batch, so cross-client batching happens inside one XLA
  matmul — the in-graph form of the paper's base-executor batching (§3.7).
* ``make_compact_train_step`` — the fine-tuning-as-a-service tick
  (``training.FinetuneEngine``): a job-masked, slot-compacted step over one
  BANK of jobs, each with its own traced hyperparameters/schedule position,
  gathered into a bucketed row batch and scattered back under a row mask.
  Runs the same per-row program as ``make_baseline_train_step``
  (``make_row_grad_fn``), which is what makes a served job's grads/params
  bitwise-equal to its dedicated run.
* ``make_multi_client_decode_step`` / ``prefill`` — inference banks sharing
  the base, one token per step per request against per-client KV caches.
* ``make_mixed_step`` — inference + fine-tuning clients time-share the base
  in one step (paper §4.4). The live-service form is
  ``training.SymbiosisEngine`` interleaving engine ticks.

The torch-like comparison baseline (each job differentiates through a
private base copy, saving activations) is ``make_baseline_train_step``'s
default (``memory_optimized=False``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.constrain import constrain_batch
from repro.config import (AdapterConfig, ModelConfig, TrainConfig, ServeConfig,
                          DENSE, MOE, VLM, HYBRID, ENCDEC)
from repro.core import adapters as adapters_lib
from repro.core.virtlayer import (make_client_ctx, make_compact_ctx,
                                  make_mixed_ctx)
from repro.models import get_model
from repro.models.losses import lm_loss
from repro.optim import adamw_init, adamw_update, adamw_update_hyper
from repro.optim.schedules import warmup_cosine


def program_named(fn, name: str):
    """Name a step function for ``jax.jit``: its program then carries
    ``jit_<name>`` on the device (a profiler trace's ``XLA Modules`` line),
    whichever closure the step function's maker returned. The computation
    is untouched."""
    fn.__name__ = fn.__qualname__ = name
    return fn


# ---------------------------------------------------------------------------
# State construction
# ---------------------------------------------------------------------------

def init_system(cfg: ModelConfig, acfg: AdapterConfig, n_clients: int, key,
                adapter_dtype=jnp.float32):
    """Returns (base_params, client_bank_adapters, opt_state_bank)."""
    k_base, k_bank = jax.random.split(key)
    model = get_model(cfg)
    base = model.init_params(k_base)
    bank = adapters_lib.init_client_bank(cfg, acfg, n_clients, k_bank, adapter_dtype)
    opt = jax.vmap(adamw_init)(bank)
    return base, bank, opt


# ---------------------------------------------------------------------------
# Multi-client fine-tuning
# ---------------------------------------------------------------------------

def make_multi_client_train_step(cfg: ModelConfig, acfg: AdapterConfig,
                                 tcfg: TrainConfig, *, moe_dispatch="scatter",
                                 capacity_factor: float = 1.25):
    model = get_model(cfg)
    ctx = make_client_ctx(cfg, acfg, memory_optimized=tcfg.memory_optimized_backward)

    def client_loss(adapter, base, batch):
        logits, aux = model.forward(base, batch, ctx, adapter,
                                    remat=tcfg.remat, moe_dispatch=moe_dispatch,
                                    capacity_factor=capacity_factor)
        return lm_loss(logits, batch["labels"], batch.get("mask"), aux)

    grad_fn = jax.value_and_grad(client_loss)

    def _grads(base, bank, batch):
        """(losses [C], grads bank-tree). With tcfg.microbatch > 0 the
        per-client batch axis is split into microbatches accumulated with
        lax.scan — adapter grads are tiny, so accumulation is nearly free
        while activation temps shrink by the microbatch factor."""
        nmb = tcfg.microbatch
        B = batch["tokens"].shape[1]
        if not nmb or nmb <= 1 or B % nmb or B == nmb:
            return jax.vmap(grad_fn, in_axes=(0, None, 0))(bank, base, batch)

        def split(x):   # [C, B, ...] -> [nmb, C, B/nmb, ...]
            return x.reshape(x.shape[0], nmb, B // nmb, *x.shape[2:]).swapaxes(0, 1)

        mb = jax.tree.map(split, batch)
        zero_g = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), bank)

        def body(carry, mbatch):
            loss_acc, g_acc = carry
            losses, grads = jax.vmap(grad_fn, in_axes=(0, None, 0))(bank, base, mbatch)
            g_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) / nmb,
                                 g_acc, grads)
            return (loss_acc + losses / nmb, g_acc), None

        (losses, grads), _ = jax.lax.scan(body, (jnp.zeros((losses_shape(bank),)),
                                                 zero_g), mb)
        return losses, grads

    def losses_shape(bank):
        return jax.tree.leaves(bank)[0].shape[0]

    def train_step(base, bank, opt, batch, step):
        """batch: pytree with leading [C, B, ...] axes; step: scalar int."""
        lr = warmup_cosine(step, tcfg.lr, tcfg.warmup_steps, tcfg.total_steps)
        losses, grads = _grads(base, bank, batch)
        new_bank, new_opt, gnorms = jax.vmap(
            lambda p, g, s: adamw_update(p, g, s, lr,
                                         weight_decay=tcfg.weight_decay,
                                         max_grad_norm=tcfg.max_grad_norm)
        )(bank, grads, opt)
        return new_bank, new_opt, {"loss": losses, "gnorm": gnorms, "lr": lr}

    return train_step


def make_row_grad_fn(cfg: ModelConfig, acfg: AdapterConfig, *,
                     remat: bool = True, memory_optimized: bool = True,
                     microbatch: int = 0, moe_dispatch: str = "scatter",
                     capacity_factor=None, differentiate_base: bool = False):
    """One JOB's loss-and-grads closure: ``fn(adapter, base, batch[B, ...])
    -> (loss, adapter_grads)``, with ``microbatch > 1`` accumulating grads
    over a ``lax.scan`` of B/microbatch-sized slices (mean of per-microbatch
    means, f32 accumulators — the same math as the bank-wide step's
    accumulation).

    This single closure is the byte-identity contract of fine-tuning as a
    service: ``make_compact_train_step`` vmaps it over the gathered bank
    rows and ``make_baseline_train_step`` runs it solo, so a job's grads in
    a bank are the SAME program as its dedicated run — equality is by
    construction, not by tolerance. ``differentiate_base=True`` additionally
    differentiates through the base tree (grads discarded), forcing
    activation residuals for every base linear — the torch-like memory
    baseline of Fig 9/10."""
    model = get_model(cfg)
    ctx = make_client_ctx(cfg, acfg, memory_optimized=memory_optimized)

    def client_loss(adapter, base, batch):
        logits, aux = model.forward(base, batch, ctx, adapter, remat=remat,
                                    moe_dispatch=moe_dispatch,
                                    capacity_factor=capacity_factor)
        return lm_loss(logits, batch["labels"], batch.get("mask"), aux)

    if differentiate_base:
        def pair_loss(adapter_and_base, batch):
            adapter, base = adapter_and_base
            return client_loss(adapter, base, batch)

        vg = jax.value_and_grad(pair_loss)

        def grad_fn(adapter, base, batch):
            l, (g_adapter, _g_base_discarded) = vg((adapter, base), batch)
            return l, g_adapter
    else:
        grad_fn = jax.value_and_grad(client_loss)

    nmb = microbatch
    if not nmb or nmb <= 1:
        return grad_fn

    def accum_grad_fn(adapter, base, batch):
        B = batch["tokens"].shape[0]
        if B % nmb or B == nmb:
            return grad_fn(adapter, base, batch)

        def split(x):   # [B, ...] -> [nmb, B/nmb, ...]
            return x.reshape(nmb, B // nmb, *x.shape[1:])

        mb = jax.tree.map(split, batch)
        zero_g = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), adapter)

        def body(carry, mbatch):
            l_acc, g_acc = carry
            l, g = grad_fn(adapter, base, mbatch)
            g_acc = jax.tree.map(lambda a, gg: a + gg.astype(jnp.float32) / nmb,
                                 g_acc, g)
            return (l_acc + l / nmb, g_acc), None

        (l, g), _ = jax.lax.scan(body, (jnp.zeros(()), zero_g), mb)
        return l, g

    return accum_grad_fn


def make_baseline_train_step(cfg: ModelConfig, acfg: AdapterConfig,
                             tcfg: TrainConfig, *,
                             memory_optimized: bool = False,
                             moe_dispatch: str = "scatter",
                             capacity_factor=None):
    """Dedicated single-job trainer — the oracle every FinetuneEngine job is
    compared against, AND (by default) the torch-like memory baseline.

    ``memory_optimized=False`` (default) differentiates through the base
    tree (grads discarded), forcing activation residuals for every base
    linear — the paper's non-memory-optimized baseline for Fig 9/10.
    ``memory_optimized=True`` runs the §3.6 client path, exactly the
    program a bank row executes. Either way the step runs the SAME
    ``make_row_grad_fn`` closure the compact multi-job step vmaps
    (``tcfg.microbatch`` accumulation included), so a job served by the
    engine must reproduce this step's grads and params bit-for-bit."""
    row_grads = make_row_grad_fn(cfg, acfg, remat=tcfg.remat,
                                 memory_optimized=memory_optimized,
                                 microbatch=tcfg.microbatch,
                                 moe_dispatch=moe_dispatch,
                                 capacity_factor=capacity_factor,
                                 differentiate_base=not memory_optimized)

    def train_step(base, adapter, opt, batch, step):
        lr = warmup_cosine(step, tcfg.lr, tcfg.warmup_steps, tcfg.total_steps)
        l, grads = row_grads(adapter, base, batch)
        adapter, opt, gnorm = adamw_update(adapter, grads, opt, lr,
                                           weight_decay=tcfg.weight_decay,
                                           max_grad_norm=tcfg.max_grad_norm)
        return adapter, opt, {"loss": l, "gnorm": gnorm, "lr": lr}

    return train_step


def make_compact_train_step(cfg: ModelConfig, acfg: AdapterConfig, *,
                            microbatch: int = 0, remat: bool = True,
                            memory_optimized: bool = True,
                            moe_dispatch: str = "scatter",
                            capacity_factor=None):
    """Job-masked, slot-compacted multi-job train step — the FinetuneEngine's
    tick over ONE bank (jobs sharing an AdapterConfig + batch shape +
    microbatching, each with its OWN AdamW state, schedule position and
    data).

    fn(base, bank, opt, batch, slots, row_mask, hyper)
      -> (new bank, new opt, metrics)

    * ``bank`` / ``opt``  — job-stacked trees with leading [cap] bank-slot
      axis; only the gathered rows' slots are ever rewritten, so slots
      outside this call (retired jobs' leftovers, other jobs between their
      admission and this tick) stay bitwise untouched — the optimizer-state
      isolation guarantee under join/leave churn.
    * ``batch``           — leaves [R, B, ...]: row i is the job in bank
      slot ``slots[i]`` feeding its OWN per-step batch. R is a call-site
      property; the engine buckets the active-job count to a few static
      sizes to bound recompilation (the training analogue of the compacted
      decode tick). ``row_mask`` False marks padding rows: their loss is
      garbage and every write they produce is dropped at the scatter.
    * ``hyper``           — per-row traced hyperparameters, [R] arrays:
      ``step`` (the job's own schedule position), ``lr``, ``warmup``,
      ``total`` (its warmup-cosine schedule), ``wd``, ``gnorm`` (clip
      threshold; inf = no clipping). Heterogeneous jobs ride one vmapped
      step because ``adamw_update_hyper`` is bitwise-equal to the static
      conditional form at every setting.

    Per-row grads come from the same ``make_row_grad_fn`` closure the solo
    ``make_baseline_train_step`` runs, vmapped with the base unbatched —
    the merged token batch hits the shared base matmuls as ONE XLA op
    (§3.7 base-executor batching) while each job's grads and updated
    adapter params stay bit-for-bit equal to its dedicated run.

    HEALTH PROBE (docs/robustness.md): ``metrics["finite"]`` is a per-row
    isfinite reduction over the row's loss and every grad leaf, computed
    inside this jitted step (no extra dispatch, no pool copy), and the
    scatter commits a row only when ``row_mask & finite`` — a row whose
    step produced NaN/Inf keeps its LAST CLEAN adapter + optimizer state
    in the bank, so the engine can retry the same step or quarantine the
    job from a clean snapshot. When every row is finite the committed
    state is bitwise what the ungated scatter produced.
    """
    row_grads = make_row_grad_fn(cfg, acfg, remat=remat,
                                 memory_optimized=memory_optimized,
                                 microbatch=microbatch,
                                 moe_dispatch=moe_dispatch,
                                 capacity_factor=capacity_factor)

    def train_step(base, bank, opt, batch, slots, row_mask, hyper):
        cap = jax.tree.leaves(bank)[0].shape[0]
        slots = slots.astype(jnp.int32)
        # gather boundary: the compacted job rows (and their batches)
        # partition over the mesh batch axes, NOT over the base's
        # tensor-parallel axes — the scatter below returns to the bank's
        # own layout. No-ops without an ambient mesh.
        params = jax.tree.map(constrain_batch, jax.tree.map(
            lambda x: x[slots], bank))
        ostate = jax.tree.map(constrain_batch, jax.tree.map(
            lambda x: x[slots], opt))
        batch = jax.tree.map(constrain_batch, batch)
        R = slots.shape[0]

        def rows_finite(row_losses, row_grads):
            # per-row non-finite probe: loss AND every grad leaf ([R, ...])
            ok = jnp.isfinite(row_losses)
            for g in jax.tree.leaves(row_grads):
                ok = ok & jnp.isfinite(g).reshape(g.shape[0], -1).all(axis=1)
            return ok

        if R == 1:
            # A one-row bucket skips the vmap entirely: vmap-of-1 still
            # traces a BATCHED program, and for MoE layers XLA fuses that
            # batched backward differently from the solo baseline program
            # at some token counts (1-2 ulp drift — see tests/test_moe.py::
            # TestVmapBitwise). Running the single row through the same
            # unbatched program the baseline runs keeps the R=1 bucket on
            # the bitwise contract for every family.
            one = lambda t: jax.tree.map(lambda x: x[0], t)
            lift = lambda t: jax.tree.map(lambda x: x[None], t)
            l1, g1 = row_grads(one(params), base, one(batch))
            lr1 = warmup_cosine(hyper["step"][0], hyper["lr"][0],
                                hyper["warmup"][0], hyper["total"][0])
            p1, o1, gn1 = adamw_update_hyper(one(params), g1, one(ostate),
                                             lr1, hyper["wd"][0],
                                             hyper["gnorm"][0])
            new_p, new_o = lift(p1), lift(o1)
            losses, gnorms, lr = l1[None], gn1[None], lr1[None]
            finite = rows_finite(losses, lift(g1))
        else:
            losses, grads = jax.vmap(row_grads, in_axes=(0, None, 0))(
                params, base, batch)
            lr = warmup_cosine(hyper["step"], hyper["lr"], hyper["warmup"],
                               hyper["total"])
            new_p, new_o, gnorms = jax.vmap(adamw_update_hyper)(
                params, grads, ostate, lr, hyper["wd"], hyper["gnorm"])
            finite = rows_finite(losses, grads)
        # commit only healthy rows: a non-finite row's slot keeps its last
        # clean state (cap is out of bounds -> scatter-drop)
        drop = jnp.where(row_mask & finite, slots, cap)

        def scatter(full, rows):
            return full.at[drop].set(rows.astype(full.dtype), mode="drop")

        new_bank = jax.tree.map(scatter, bank, new_p)
        new_opt = jax.tree.map(scatter, opt, new_o)
        return new_bank, new_opt, {"loss": losses, "gnorm": gnorms, "lr": lr,
                                   "finite": finite}

    # train_lora_r16, train_ia3, train_prefix: the banks of one engine are
    # told apart on the device by method and LoRA rank
    return program_named(train_step, f"train_lora_r{acfg.rank}"
                         if acfg.method == "lora" else f"train_{acfg.method}")


# ---------------------------------------------------------------------------
# Multi-client inference
# ---------------------------------------------------------------------------

def make_multi_client_prefill(cfg: ModelConfig, acfg: Optional[AdapterConfig],
                              scfg: ServeConfig, **ctx_kw):
    model = get_model(cfg)
    ctx = make_client_ctx(cfg, acfg, **ctx_kw)

    def prefill(base, bank, caches, batch):
        """batch tokens [C, B, S]; caches with leading [C]."""
        def one(adapter, cache, b):
            return model.prefill(base, b, cache, ctx, adapter)
        return jax.vmap(one, in_axes=(0, 0, 0))(bank, caches, batch)

    return prefill


def make_multi_client_decode_step(cfg: ModelConfig, acfg: Optional[AdapterConfig],
                                  scfg: ServeConfig, *, ring: bool = False, **ctx_kw):
    model = get_model(cfg)
    ctx = make_client_ctx(cfg, acfg, **ctx_kw)
    kw = {"ring": True} if ring else {}

    def decode(base, bank, caches, tokens):
        """tokens [C, B] -> (logits [C, B, V], new caches)."""
        def one(adapter, cache, tok):
            return model.decode_step(base, cache, tok, ctx, adapter, **kw)
        return jax.vmap(one, in_axes=(0, 0, 0))(bank, caches, tokens)

    return decode


def serve_cache_kwargs(cfg: ModelConfig, scfg: ServeConfig, *,
                       pool_pages: Optional[int] = None):
    """Cache-construction kwargs implied by a ServeConfig for this family.

    Paging applies to the attention-bearing families only (recurrent
    families carry O(1) state — nothing to page); int8 KV quantization to
    the pure-KV families (dense/MoE/VLM). ``pool_pages`` overrides the
    pool sizing (the engine passes its allocator's pool size; slot-axis
    derivation passes 1 so pool shapes don't scale with the probe batch)."""
    kw = {}
    if scfg.page_block and cfg.arch in (DENSE, MOE, VLM, HYBRID, ENCDEC):
        kw["page_block"] = scfg.page_block
        if pool_pages is not None:
            kw["pool_pages"] = pool_pages
        elif scfg.pool_pages:
            kw["pool_pages"] = scfg.pool_pages
    if scfg.kv_quant and cfg.arch in (DENSE, MOE, VLM):
        kw["quant"] = True
    return kw


def cache_slot_axes(cfg: ModelConfig, max_seq: int, **cache_kw):
    """Per-leaf *slot axis* map for one client's decode cache.

    Cache trees are family-specific (KV tensors carry the sequence-slot
    [batch] axis at axis 1 under a leading layer/group axis, ``pos`` carries
    it at axis 0, pre-layer KV at axis 0, ...). The engine needs to merge /
    zero individual slots without knowing the family, so we derive the axis
    structurally: build the cache at batch 1 and batch 2 and record, per
    leaf, the axis where the shapes differ. Leaves whose shape does NOT
    depend on the batch — the paged layout's shared page pools — map to
    ``None``: they have no slot axis, are never zeroed per slot, and a
    masked step's pool writes are already gated by the active mask inside
    the model, so merges take the new value wholesale. ``block_tbl`` is
    likewise ``None``: it is engine-managed state that models pass through
    untouched. Returns a pytree of Optional[int] with the cache's
    structure. Shapes only — ``eval_shape`` never allocates the
    (potentially huge) caches."""
    model = get_model(cfg)
    if cache_kw.get("page_block"):
        # pin the pool size so it can't scale with the probe batch (auto
        # sizing is batch * n_blocks, which would masquerade as a slot axis)
        cache_kw = dict(cache_kw, pool_pages=cache_kw.get("pool_pages") or 1)
    a = jax.eval_shape(lambda: model.init_cache(1, max_seq, **cache_kw))
    b = jax.eval_shape(lambda: model.init_cache(2, max_seq, **cache_kw))

    def axis(x, y):
        for i, (m, n) in enumerate(zip(x.shape, y.shape)):
            if m != n:
                return i
        return None                      # batch-independent leaf (page pool)

    axes = jax.tree.map(axis, a, b)
    if isinstance(axes, dict) and "block_tbl" in axes:
        axes["block_tbl"] = None
    return axes


def _slot_mask(mask, ax, ndim):
    """Reshape a [n_slots] mask so it broadcasts along slot axis ``ax`` of an
    ``ndim``-rank cache leaf."""
    shape = [1] * ndim
    shape[ax] = mask.shape[-1]
    return mask.reshape(shape)


def make_client_prefill(cfg: ModelConfig, acfg: Optional[AdapterConfig],
                        scfg: ServeConfig, **ctx_kw):
    """Masked single-client prefill — the engine's admission fast path.

    Unlike ``make_multi_client_prefill`` (which runs the whole bank and
    wastes C× base compute per admitted request), this runs the model ONCE
    for the admitted client and scatters the result into the bank caches:

      fn(base, bank, caches, c, a, tokens, lengths, slot_mask)
        -> (logits [max_b, V], new bank caches)

    * ``c``         — traced client index into the CACHES (one compile
                      serves every client).
    * ``a``         — traced adapter index into ``bank``. A single-bank
                      engine passes ``a == c``; a mixed-method engine
                      passes the client's index WITHIN its own method bank
                      (the caches stay global across banks, the adapter
                      trees do not).
    * ``tokens``    — [max_b, S_pad]; rows being admitted carry the prompt
                      (right-padded to the engine's jit bucket), other rows
                      are dummies.
    * ``lengths``   — [max_b] true prompt lengths; logits are gathered at
                      each row's last *real* position and cache ``pos``
                      starts there. Right-padding is exact for attention
                      families (causal masking + decode's write-before-read
                      overwrites stale pad K/V); recurrent families (hybrid,
                      RWKV) must be called with S_pad == S because pads
                      would pollute the carried state.
    * ``slot_mask`` — [max_b] bool; True rows are (re-)initialized: their
                      state is zeroed before the prefill (so a finished
                      sequence's stale recurrent state never leaks into the
                      slot's next occupant) and only their cache entries are
                      written back — other slots' in-flight state is
                      untouched, which is what makes mid-stream join work.
    """
    model = get_model(cfg)
    ctx = make_client_ctx(cfg, acfg, **ctx_kw)
    cache_kw = serve_cache_kwargs(cfg, scfg, pool_pages=1)
    slot_axes = cache_slot_axes(cfg, scfg.max_seq, **cache_kw)
    page_axes = (cache_page_axes(cfg, scfg.max_seq, **cache_kw)
                 if "page_block" in cache_kw
                 else jax.tree.map(lambda ax: None, slot_axes))

    def prefill_one(base, bank, caches, c, a, tokens, lengths, slot_mask):
        adapter = jax.tree.map(lambda x: x[a], bank) if bank is not None else None

        def slice_c(x, ax, pax):
            # global page pools have no client axis; everything else
            # (per-slot leaves, the client's block-table rows) is sliced
            return x if pax is not None else x[c]

        old = jax.tree.map(slice_c, caches, slot_axes, page_axes)

        def zero_slots(x, ax):
            if ax is None:    # shared page pool / block table: no slot rows
                return x      # to zero — stale pages are masked by position
            return jnp.where(_slot_mask(slot_mask, ax, x.ndim),
                             jnp.zeros((), x.dtype), x)

        cleared = jax.tree.map(zero_slots, old, slot_axes)
        logits, new = model.prefill(base, {"tokens": tokens}, cleared, ctx,
                                    adapter, lengths=lengths)

        def merge(o, n, ax):
            if ax is None:    # pool writes were already bounded by lengths
                return n
            return jnp.where(_slot_mask(slot_mask, ax, o.ndim), n, o)

        merged = jax.tree.map(merge, old, new, slot_axes)

        def write_back(full, one, ax, pax):
            if pax is not None:
                return one                     # global pool: already merged
            return full.at[c].set(one)

        new_caches = jax.tree.map(write_back, caches, merged, slot_axes,
                                  page_axes)
        return logits, new_caches

    return prefill_one


def make_masked_decode_step(cfg: ModelConfig, acfg: Optional[AdapterConfig],
                            scfg: ServeConfig, *, ring: bool = False, **ctx_kw):
    """Bank-wide decode tick with per-slot advance control.

    fn(base, bank, caches, tokens, active) -> (logits [C, B, V], new caches)

    ``active`` [C, B] bool marks the sequence slots that are decoding this
    tick; every other slot's cache (including its position counter) is left
    exactly as it was, so clients can run at different rates and sequences
    can join/leave mid-stream. The merge happens inside the jitted step —
    one dispatch per tick instead of a host-side tree traversal.

    Paged caches (scfg.page_block > 0) can't express the merge as a
    per-slot select — the page pool is GLOBAL (one flat pool, clients own
    page ranges; see init_client_caches) — so the active rows are threaded
    INTO the model step instead: inactive slots' pool writes are dropped at
    the scatter (blocks.paged_token_write) and the merge takes pool leaves
    wholesale. The pool rides the client vmap UNBATCHED: the write op and
    the table-aware attention kernel both carry custom_vmap rules that
    flatten the client axis into rows against the shared pool, so this
    bank-wide step lowers to exactly the computation the compacted step
    (make_compact_decode_step) runs on the active rows — byte-identity
    between the two is by construction, not by numerical luck."""
    model = get_model(cfg)
    ctx = make_client_ctx(cfg, acfg, **ctx_kw)
    kw = {"ring": True} if ring else {}
    cache_kw = serve_cache_kwargs(cfg, scfg, pool_pages=1)
    paged = "page_block" in cache_kw
    slot_axes = cache_slot_axes(cfg, scfg.max_seq, **cache_kw)
    if paged:
        page_axes = cache_page_axes(cfg, scfg.max_seq, **cache_kw)
        # global pools are shared across the client vmap (in/out axis None)
        cache_axes = jax.tree.map(
            lambda x, pax: None if pax is not None else 0,
            jax.eval_shape(lambda: get_model(cfg).init_cache(
                1, scfg.max_seq, **cache_kw)), page_axes)

    def decode(base, bank, caches, tokens, active):
        if paged:
            def one(adapter, cache, tok, act):
                return model.decode_step(base, cache, tok, ctx, adapter,
                                         active=act, **kw)
            logits, new_caches = jax.vmap(
                one, in_axes=(0, cache_axes, 0, 0),
                out_axes=(0, cache_axes))(bank, caches, tokens, active)
        else:
            def one(adapter, cache, tok):
                return model.decode_step(base, cache, tok, ctx, adapter, **kw)
            logits, new_caches = jax.vmap(one, in_axes=(0, 0, 0))(bank, caches, tokens)

        def merge(o, n, ax):
            if ax is None:    # pool writes already active-gated in the model
                return n
            shape = [1] * o.ndim
            shape[0] = active.shape[0]
            shape[ax + 1] = active.shape[1]
            return jnp.where(active.reshape(shape), n, o)

        return logits, jax.tree.map(merge, caches, new_caches, slot_axes)

    return decode


def cache_page_axes(cfg: ModelConfig, max_seq: int, **cache_kw):
    """Per-leaf *page-pool axis* map for one client's PAGED decode cache.

    The structural twin of ``cache_slot_axes``: build the cache at
    ``pool_pages`` 1 and 2 and record, per leaf, the axis whose extent
    changed — that is the axis page pools stack their pages on (layer-
    stacked pools carry it behind the leading layer/group axis; pre-layer
    pools carry it at axis 0). Per-slot leaves (positions, recurrent state,
    cross-attention caches) and the block table don't scale with the pool
    and map to ``None``. Shapes only — nothing is allocated."""
    assert cache_kw.get("page_block"), "page axes exist only for paged caches"
    model = get_model(cfg)
    a = jax.eval_shape(lambda: model.init_cache(
        2, max_seq, **dict(cache_kw, pool_pages=1)))
    b = jax.eval_shape(lambda: model.init_cache(
        2, max_seq, **dict(cache_kw, pool_pages=2)))

    def axis(x, y):
        for i, (m, n) in enumerate(zip(x.shape, y.shape)):
            if m != n:
                return i
        return None

    return jax.tree.map(axis, a, b)


def _fold_pool_leaf(x, pax):
    """Fold a bank leaf's leading client axis into its page axis:
    [C, .., P@pax+1, ..] -> [.., C*P@pax, ..] (the global-pool layout
    convention — client c owns page range [c*P, (c+1)*P)). ``pax`` is the
    page axis of the PER-CLIENT leaf; None leaves pass through."""
    if pax is None:
        return x
    rest = list(x.shape)
    P = rest.pop(pax + 1)
    C = rest.pop(0)
    y = jnp.moveaxis(x, pax + 1, 1).reshape((C * P,) + tuple(rest))
    return jnp.moveaxis(y, 0, pax)


def stack_client_caches(cfg: ModelConfig, max_seq: int, per_client, **cache_kw):
    """Stack per-client model caches (e.g. after standalone per-client
    prefills on identity tables) into the BANK layout: per-slot leaves gain
    a leading client axis; paged pools fold into the one global flat pool
    (client c's pages land in [c*P, (c+1)*P)) and block tables are offset
    to global page ids. The inverse convention of ``init_client_caches``."""
    caches = jax.tree.map(lambda *xs: jnp.stack(xs), *per_client)
    if not cache_kw.get("page_block"):
        return caches
    page_axes = cache_page_axes(cfg, max_seq, **cache_kw)
    C = len(per_client)
    P = max(jax.tree.leaves(jax.tree.map(
        lambda x, pax: None if pax is None else x.shape[pax + 1],
        caches, page_axes)))
    caches = jax.tree.map(_fold_pool_leaf, caches, page_axes)
    caches["block_tbl"] = (caches["block_tbl"]
                           + (jnp.arange(C, dtype=jnp.int32) * P)[:, None, None])
    return caches


def make_compact_decode_step(cfg: ModelConfig, acfg, scfg: ServeConfig,
                             probe: bool = False, **ctx_kw):
    """Compute-proportional decode tick: run ONLY the actively decoding
    sequence slots, gathered across clients into one dense batch.

    Single-method (``acfg`` an AdapterConfig or None):

      fn(base, bank, caches, tokens, clients, slots, row_mask)
        -> (logits [n_rows, V], new bank caches)

    MIXED-METHOD (``acfg`` a tuple/list of AdapterConfigs — the serving
    engine's heterogeneous bank registry):

      fn(base, banks, caches, tokens, clients, slots, methods, locals_,
         row_mask) -> (logits [n_rows, V], new bank caches)

    where ``banks`` is the matching tuple of client-stacked adapter trees,
    ``methods[i]`` names row i's bank and ``locals_[i]`` its client index
    WITHIN that bank (``clients[i]`` stays the GLOBAL cache client index).
    One tick then carries several PEFT methods at once: LoRA rows keep the
    SGMV path (dead ids for other rows), IA3/prefix rows get per-row
    gathers keyed by their method id, and every application is gated by a
    membership select — so each row's math is byte-identical to its solo
    single-method run whatever its neighbours' methods are
    (``virtlayer.make_mixed_ctx`` / ``adapters.compact_mixed_bank``).

    * ``tokens``/``clients``/``slots``/``row_mask`` — [n_rows] arrays; row i
      is sequence slot ``slots[i]`` of client ``clients[i]`` feeding
      ``tokens[i]``. The row count is a call-site property (jax retraces
      per shape; the engine buckets the active count to a few static sizes
      to bound recompilation). ``row_mask`` False marks padding rows: their
      logits are garbage and every write they produce is dropped.
    * Requires the PAGED KV layout (``scfg.page_block > 0``): per-slot
      leaves (positions, recurrent state, cross-attention caches) are
      gathered per row and scattered back under the row mask, while the
      GLOBAL page pools (see ``init_client_caches``) pass through untouched
      — the gathered block-table rows already carry global page ids, so
      attention reads/writes land in the original pool pages through the
      table-aware kernel. The masked bank-wide decode lowers to exactly
      this flattened computation (the kernel's and the token write's
      custom_vmap rules), which makes the two paths byte-identical: the
      policy/occupancy only decides which rows exist, never their math.
    * Per-row client adapters are applied by ``make_compact_ctx`` — LoRA
      through the SGMV kernel (one adapter per row), IA3/prefix by per-row
      gathers. FLOPs and HBM traffic of base matmuls, adapter deltas and
      attention all scale with ``n_rows``, not with the bank size.
    * ``probe=True`` (HEALTH PROBE, docs/robustness.md) additionally
      returns a per-row ``finite`` [n_rows] bool — an isfinite reduction
      over the row's logits, computed inside the same jitted step — as
      ``(logits, finite, new caches)``. The logits and cache math are
      bit-identical to the unprobed step; the serving engine uses the flag
      to quarantine a request whose stream went non-finite without an
      extra device round-trip.
    """
    mixed = isinstance(acfg, (tuple, list))
    acfgs = tuple(acfg) if mixed else None
    model = get_model(cfg)
    cache_kw = serve_cache_kwargs(cfg, scfg, pool_pages=1)
    if "page_block" not in cache_kw:
        raise ValueError(
            "compact decode requires the paged KV layout (ServeConfig."
            "page_block > 0 on an attention-bearing family); the dense "
            "layout keeps the masked bank-wide step")
    slot_axes = cache_slot_axes(cfg, scfg.max_seq, **cache_kw)
    page_axes = cache_page_axes(cfg, scfg.max_seq, **cache_kw)
    # block_tbl is engine-managed: excluded from the generic leaf handling
    slot_axes.pop("block_tbl", None)
    page_axes.pop("block_tbl", None)

    def _rest(x, lifted):
        shape = list(x.shape)
        del shape[lifted], shape[0]
        return tuple(shape)

    def _gather_caches(caches, rows, C, B):
        inner = {k: v for k, v in caches.items() if k != "block_tbl"}

        def gather(x, ax, pax):
            if pax is not None:      # global pool: flat already, zero copies
                return x
            if ax is not None:       # per-slot leaf: [C, .., B@ax, ..] -> rows
                y = jnp.moveaxis(x, ax + 1, 1).reshape((C * B,) + _rest(x, ax + 1))
                # gather boundary: compacted rows partition over the mesh
                # batch axes (never the base's tensor axes) — no-op off-mesh
                return constrain_batch(jnp.moveaxis(y[rows], 0, ax), ax)
            raise ValueError("paged cache leaf with neither slot nor page axis")

        compact_cache = jax.tree.map(gather, inner, slot_axes, page_axes)
        # table rows already hold global page ids (allocator page ranges)
        compact_cache["block_tbl"] = constrain_batch(
            caches["block_tbl"].reshape(C * B, -1)[rows])
        return inner, compact_cache

    def _scatter_caches(inner, new_compact, rows, row_mask, C, B):
        new_compact = {k: v for k, v in new_compact.items() if k != "block_tbl"}
        drop_rows = jnp.where(row_mask, rows, C * B)     # C*B is out of bounds

        def scatter(old, new, ax, pax):
            if pax is not None:
                # pool writes were row-masked inside paged_token_write
                return new
            rest = _rest(old, ax + 1)
            flat = jnp.moveaxis(old, ax + 1, 1).reshape((C * B,) + rest)
            vals = jnp.moveaxis(new, ax, 0)
            flat = flat.at[drop_rows].set(vals.astype(flat.dtype), mode="drop")
            return jnp.moveaxis(flat.reshape((C, B) + rest), 1, ax + 1)

        return jax.tree.map(scatter, inner, new_compact, slot_axes, page_axes)

    def compact(base, bank, caches, tokens, clients, slots, row_mask):
        C, B = caches["pos"].shape
        clients = clients.astype(jnp.int32)
        slots = slots.astype(jnp.int32)
        rows = clients * B + slots
        inner, compact_cache = _gather_caches(caches, rows, C, B)
        ctx = make_client_ctx(cfg, None, **ctx_kw) if bank is None else \
            make_compact_ctx(cfg, acfg, clients, **ctx_kw)
        adapter = adapters_lib.compact_adapter_bank(bank, clients)
        logits, new_compact = model.decode_step(base, compact_cache,
                                                constrain_batch(tokens),
                                                ctx, adapter, active=row_mask)
        new_inner = _scatter_caches(inner, new_compact, rows, row_mask, C, B)
        return _out(constrain_batch(logits),
                    dict(new_inner, block_tbl=caches["block_tbl"]))

    def _out(logits, new_caches):
        if probe:
            return logits, jnp.isfinite(logits).all(axis=-1), new_caches
        return logits, new_caches

    def compact_mixed(base, banks, caches, tokens, clients, slots, methods,
                      locals_, row_mask):
        C, B = caches["pos"].shape
        clients = clients.astype(jnp.int32)
        slots = slots.astype(jnp.int32)
        methods = methods.astype(jnp.int32)
        locals_ = locals_.astype(jnp.int32)
        rows = clients * B + slots
        inner, compact_cache = _gather_caches(caches, rows, C, B)
        ctx = make_mixed_ctx(cfg, acfgs, locals_, methods, **ctx_kw)
        adapter = adapters_lib.compact_mixed_bank(banks, locals_, methods)
        logits, new_compact = model.decode_step(base, compact_cache,
                                                constrain_batch(tokens),
                                                ctx, adapter, active=row_mask)
        new_inner = _scatter_caches(inner, new_compact, rows, row_mask, C, B)
        return _out(constrain_batch(logits),
                    dict(new_inner, block_tbl=caches["block_tbl"]))

    return program_named(compact_mixed if mixed else compact,
                         "compact_decode")


def make_compact_prefill(cfg: ModelConfig, acfg, scfg: ServeConfig,
                         probe: bool = False, ext_blocks: int = 0, **ctx_kw):
    """Cross-client compacted PREFILL: every same-tick admission — across
    clients and, in the mixed registry, across banks — rides ONE ragged
    jit-bucketed batch (the admission analogue of
    ``make_compact_decode_step``).

    Single-method (``acfg`` an AdapterConfig or None):

      fn(base, bank, caches, tokens, lengths, starts, clients, slots,
         row_mask) -> (logits [n_rows, V], new bank caches)

    MIXED-METHOD (``acfg`` a tuple/list of AdapterConfigs):

      fn(base, banks, caches, tokens, lengths, starts, clients, slots,
         methods, locals_, row_mask) -> (logits [n_rows, V], new caches)

    * ``tokens`` [n_rows, S_pad] right-padded prompts; ``lengths`` [n_rows]
      true suffix lengths; ``starts`` [n_rows] tokens ALREADY cached in the
      row's mapped shared-prefix pages (0 = full prefill). Row i is slot
      ``slots[i]`` of client ``clients[i]``; ``row_mask`` False marks
      padding rows (length 0, every write dropped at the scatter).
    * ``ext_blocks`` (static, a jit bucket) is the number of leading
      block-table entries gathered pre-scan as read-only prefix K/V lanes
      (``model.prefill(starts=, ext_blocks=)``); 0 compiles the exact
      full-prefill program. Rows with fewer cached blocks mask unused
      lanes by position — exact-zero softmax weight, so compacted+shared
      output is bitwise the per-client no-sharing prefill
      (docs/prefix_cache.md).
    * Per-row adapters use the same SGMV / per-row-gather machinery as the
      compacted decode (LoRA blocks are S_pad tokens wide here); mixed
      rows gate every application by bank membership.
    * ``probe=True`` returns ``(logits, finite [n_rows] bool, caches)`` —
      the admission health probe, same contract as the decode step.
    * Requires the paged layout on a pure-KV attention family (dense /
      MoE / VLM): recurrent and cross-attention families carry per-slot
      state the cross-client gather cannot zero per row, and stay on the
      per-client admission path."""
    mixed = isinstance(acfg, (tuple, list))
    acfgs = tuple(acfg) if mixed else None
    model = get_model(cfg)
    cache_kw = serve_cache_kwargs(cfg, scfg, pool_pages=1)
    if "page_block" not in cache_kw:
        raise ValueError(
            "compact prefill requires the paged KV layout (ServeConfig."
            "page_block > 0 on an attention-bearing family)")
    if cfg.arch not in (DENSE, MOE, VLM):
        raise ValueError(
            f"compact prefill serves the pure-KV families (dense/MoE/VLM); "
            f"{cfg.arch} admissions stay on the per-client prefill path")
    if ext_blocks and cache_kw.get("quant"):
        raise ValueError("shared-prefix prefill (ext_blocks > 0) requires "
                         "an unquantized KV cache")
    slot_axes = cache_slot_axes(cfg, scfg.max_seq, **cache_kw)
    page_axes = cache_page_axes(cfg, scfg.max_seq, **cache_kw)
    slot_axes.pop("block_tbl", None)
    page_axes.pop("block_tbl", None)

    def _rest(x, lifted):
        shape = list(x.shape)
        del shape[lifted], shape[0]
        return tuple(shape)

    def _gather_caches(caches, rows, C, B):
        inner = {k: v for k, v in caches.items() if k != "block_tbl"}

        def gather(x, ax, pax):
            if pax is not None:      # global pool: flat already, zero copies
                return x
            if ax is not None:
                y = jnp.moveaxis(x, ax + 1, 1).reshape((C * B,) + _rest(x, ax + 1))
                return constrain_batch(jnp.moveaxis(y[rows], 0, ax), ax)
            raise ValueError("paged cache leaf with neither slot nor page axis")

        compact_cache = jax.tree.map(gather, inner, slot_axes, page_axes)
        compact_cache["block_tbl"] = constrain_batch(
            caches["block_tbl"].reshape(C * B, -1)[rows])
        return inner, compact_cache

    def _scatter_caches(inner, new_compact, rows, row_mask, C, B):
        new_compact = {k: v for k, v in new_compact.items() if k != "block_tbl"}
        drop_rows = jnp.where(row_mask, rows, C * B)     # C*B is out of bounds

        def scatter(old, new, ax, pax):
            if pax is not None:
                # pool writes were bounded by each row's true length inside
                # paged_prefill_write (padding rows carry length 0)
                return new
            rest = _rest(old, ax + 1)
            flat = jnp.moveaxis(old, ax + 1, 1).reshape((C * B,) + rest)
            vals = jnp.moveaxis(new, ax, 0)
            flat = flat.at[drop_rows].set(vals.astype(flat.dtype), mode="drop")
            return jnp.moveaxis(flat.reshape((C, B) + rest), 1, ax + 1)

        return jax.tree.map(scatter, inner, new_compact, slot_axes, page_axes)

    def _out(logits, new_caches):
        if probe:
            return logits, jnp.isfinite(logits).all(axis=-1), new_caches
        return logits, new_caches

    def _run(base, caches, tokens, lengths, starts, clients, slots,
             row_mask, ctx, adapter):
        C, B = caches["pos"].shape
        rows = clients.astype(jnp.int32) * B + slots.astype(jnp.int32)
        inner, compact_cache = _gather_caches(caches, rows, C, B)
        logits, new_compact = model.prefill(
            base, {"tokens": constrain_batch(tokens)}, compact_cache, ctx,
            adapter, lengths=lengths,
            starts=starts.astype(jnp.int32), ext_blocks=ext_blocks)
        new_inner = _scatter_caches(inner, new_compact, rows, row_mask, C, B)
        return _out(constrain_batch(logits),
                    dict(new_inner, block_tbl=caches["block_tbl"]))

    def compact(base, bank, caches, tokens, lengths, starts, clients, slots,
                row_mask):
        clients = clients.astype(jnp.int32)
        ctx = make_client_ctx(cfg, None, **ctx_kw) if bank is None else \
            make_compact_ctx(cfg, acfg, clients, **ctx_kw)
        adapter = adapters_lib.compact_adapter_bank(bank, clients)
        return _run(base, caches, tokens, lengths, starts, clients, slots,
                    row_mask, ctx, adapter)

    def compact_mixed(base, banks, caches, tokens, lengths, starts, clients,
                      slots, methods, locals_, row_mask):
        methods = methods.astype(jnp.int32)
        locals_ = locals_.astype(jnp.int32)
        ctx = make_mixed_ctx(cfg, acfgs, locals_, methods, **ctx_kw)
        adapter = adapters_lib.compact_mixed_bank(banks, locals_, methods)
        return _run(base, caches, tokens, lengths, starts,
                    clients.astype(jnp.int32), slots, row_mask, ctx, adapter)

    return program_named(compact_mixed if mixed else compact,
                         "compact_prefill")


def make_page_copy(cfg: ModelConfig, scfg: ServeConfig):
    """Copy-on-write primitive: duplicate ONE global pool page in place.

    fn(caches, src, dst) -> caches with page ``dst`` holding a bitwise copy
    of page ``src`` on every pool leaf (the layer axis is explicit on the
    stored leaves, so one dynamic slice/update per leaf copies the page at
    every layer at once). ``src``/``dst`` are traced int32 scalars — one
    compile serves every CoW admission. Non-pool leaves (positions, block
    tables) pass through untouched; the engine jits this with the caches
    donated, so the copy is a page-sized in-place write, never a pool
    materialization (docs/prefix_cache.md)."""
    cache_kw = serve_cache_kwargs(cfg, scfg, pool_pages=1)
    if "page_block" not in cache_kw:
        raise ValueError("page copy exists only for the paged KV layout")
    page_axes = cache_page_axes(cfg, scfg.max_seq, **cache_kw)

    def copy_page(caches, src, dst):
        src = src.astype(jnp.int32)
        dst = dst.astype(jnp.int32)

        def cp(x, pax):
            if pax is None:
                return x
            page = jax.lax.dynamic_slice_in_dim(x, src, 1, axis=pax)
            return jax.lax.dynamic_update_slice_in_dim(x, page, dst, axis=pax)

        return jax.tree.map(cp, caches, page_axes)

    return copy_page


def init_client_caches(cfg: ModelConfig, n_clients: int, batch: int, max_seq: int,
                       dtype=None, *, window: int = 0, quant: bool = False,
                       page_block: int = 0, pool_pages: int = 0):
    """Bank caches: per-slot leaves carry a leading client axis; PAGED pools
    are stored GLOBALLY FLAT — the client axis is folded into the page axis
    once at construction ([C, .., P, ..] -> [.., C*P, ..]) and per-client
    ownership becomes an allocator convention (client c owns page range
    [c*P, (c+1)*P)), not a tensor axis. That is what keeps the decode tick
    compute-proportional: neither the masked step (vmapped with the pool
    unbatched) nor the compacted step ever reshapes or copies the pool —
    block tables simply carry global page ids."""
    model = get_model(cfg)
    kw = {}
    if window:
        kw["window"] = window
    if quant:
        kw["quant"] = True
    if page_block:
        kw["page_block"] = page_block
        if pool_pages:
            kw["pool_pages"] = pool_pages
    one = model.init_cache(batch, max_seq, dtype, **kw)
    caches = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n_clients,) + x.shape)
                          .copy(), one)
    if page_block:
        page_axes = cache_page_axes(cfg, max_seq, **kw)
        caches = jax.tree.map(_fold_pool_leaf, caches, page_axes)
    return caches


# ---------------------------------------------------------------------------
# Mixed inference + fine-tuning (paper §4.4)
# ---------------------------------------------------------------------------

def make_mixed_step(cfg: ModelConfig, acfg: AdapterConfig, tcfg: TrainConfig,
                    scfg: ServeConfig):
    """One step: FT clients take a train step while inference clients decode,
    all against the same resident base params."""
    train_step = make_multi_client_train_step(cfg, acfg, tcfg)
    decode_step = make_multi_client_decode_step(cfg, acfg, scfg)

    def mixed(base, ft_bank, ft_opt, ft_batch, inf_bank, inf_caches, inf_tokens, step):
        ft_bank, ft_opt, metrics = train_step(base, ft_bank, ft_opt, ft_batch, step)
        logits, inf_caches = decode_step(base, inf_bank, inf_caches, inf_tokens)
        return ft_bank, ft_opt, inf_caches, logits, metrics

    return mixed

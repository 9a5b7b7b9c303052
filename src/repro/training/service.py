"""SymbiosisEngine: inference and fine-tuning time-sharing ONE frozen base.

The paper's full service shape (§4.4): a provider keeps a single resident
copy of the base params and multiplexes it between a ``ServingEngine``
(continuous-batching decode over adapter clients) and a ``FinetuneEngine``
(fine-tuning as a service over PEFT jobs) — instead of deploying one model
replica per workload. This wrapper interleaves the two engines' ticks;
because the base is frozen and each engine owns its client-side state,
interleaving changes WHEN work runs, never its math: serving outputs and
every job's training trajectory are bit-for-bit identical to running each
engine alone (asserted in tests/test_finetune_engine.py and the tier2
mixed-workload sweep).
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.core.engine_spec import EngineSpec
from repro.faults.health import AdmissionStall
from repro.serving.engine import Request, ServingEngine
from repro.training.engine import FinetuneEngine
from repro.training.job import FinetuneJob


class SymbiosisEngine:
    """Tick-interleaves a serving engine and a fine-tuning engine that close
    over the SAME base-parameter tree (checked leaf-by-leaf at
    construction — a copy would silently double the base HBM and break the
    whole point)."""

    def __init__(self, serving: Optional[ServingEngine] = None,
                 finetune: Optional[FinetuneEngine] = None, *,
                 train_every: int = 1):
        if serving is None and finetune is None:
            raise ValueError("need at least one of serving / finetune")
        if serving is not None and finetune is not None:
            s_leaves = jax.tree.leaves(serving.base)
            f_leaves = jax.tree.leaves(finetune.base)
            if len(s_leaves) != len(f_leaves) or any(
                    a is not b for a, b in zip(s_leaves, f_leaves)):
                raise ValueError(
                    "serving and finetune engines must share ONE frozen "
                    "base (identical param arrays, not copies)")
        self.serving = serving
        self.finetune = finetune
        self.train_every = max(1, train_every)
        self.stats = {"ticks": 0, "decode_ticks": 0, "train_ticks": 0,
                      "admission_stalls": 0}

    @classmethod
    def from_spec(cls, spec: EngineSpec, base_params, *,
                  serving_banks=None, router=None, train_every: int = 1,
                  policy: Optional[str] = None, obs=None, **serving_kw):
        """Build the full symbiotic service from ONE ``EngineSpec``: a
        ``ServingEngine`` when ``spec.serve`` is set (over ``serving_banks``
        — one client-stacked adapter tree per ``spec.banks`` entry), a
        ``FinetuneEngine`` when ``spec.finetune`` is set, both closing over
        the SAME base tree. Under ``spec.mesh`` the base is sharded ONCE
        here; the engines' own placement is idempotent and identity-
        preserving, so the shared-base leaf check still holds. One ``obs``
        (docs/observability.md) is shared by both engines — their spans,
        metrics and events land in a single registry/event log, labelled
        ``serving`` / ``finetune``."""
        if spec.mesh is not None:
            from repro.launch import shardings
            base_params = shardings.shard_base_params(
                spec.cfg, spec.mesh, base_params,
                replicate=spec.replicate_base)
        serving = None
        if spec.serve is not None:
            if serving_banks is None:
                raise ValueError("spec.serve is set: pass serving_banks= "
                                 "(one adapter tree per spec bank)")
            serving = ServingEngine(spec, base_params, serving_banks,
                                    router=router, policy=policy, obs=obs,
                                    **serving_kw)
        finetune = None
        if spec.finetune is not None:
            finetune = FinetuneEngine(spec, base_params, router=router,
                                      obs=obs)
        return cls(serving=serving, finetune=finetune,
                   train_every=train_every)

    # ------------------------------------------------------------------
    def submit(self, item):
        """Route a ``Request`` to serving, a ``FinetuneJob`` to training."""
        if isinstance(item, Request):
            if self.serving is None:
                raise ValueError("no serving engine attached")
            self.serving.submit(item)
        elif isinstance(item, FinetuneJob):
            if self.finetune is None:
                raise ValueError("no finetune engine attached")
            self.finetune.submit(item)
        else:
            raise TypeError(f"cannot route {type(item).__name__}")

    def tick(self) -> bool:
        """One service tick: a decode tick (if serving work exists) then
        ``train_every`` train ticks (if jobs exist). Returns True while
        either engine still has work.

        Each engine's standalone stuck detection (``AdmissionStall``: "can
        never be admitted") assumes nothing outside itself will ever free
        capacity. Under a
        SHARED PlacementRouter that assumption is wrong in exactly this
        configuration — a queued request may be waiting on HBM pinned by a
        fine-tuning job (or vice versa) — so a stall in one engine is
        fatal only when the OTHER engine holds nothing that could free.
        Every other error, a device or compile error among them,
        propagates."""
        did = False
        if self.serving is not None and self.serving.pending():
            try:
                self.serving.service_tick()
                self.stats["decode_ticks"] += 1
                did = True
            except AdmissionStall:
                if not (self.finetune is not None and self.finetune.n_active):
                    raise          # nothing training-side will ever free
                self.stats["admission_stalls"] += 1
        for _ in range(self.train_every):
            if self.finetune is not None and self.finetune.pending():
                try:
                    self.finetune.train_tick()
                    self.stats["train_ticks"] += 1
                    did = True
                except AdmissionStall:
                    if not (self.serving is not None
                            and self.serving.n_inflight):
                        raise      # nothing serving-side will ever free
                    self.stats["admission_stalls"] += 1
        if did:
            self.stats["ticks"] += 1
        return did

    def drain_events(self, *, client=None, kind=None) -> list:
        """Merged client-visible event feed (docs/observability.md): drain
        both engines' structured events, ordered by global sequence number.
        When the engines share one ``Obs`` (the ``from_spec`` path) the
        underlying log is drained once; distinct obs objects are each
        drained and the results merged."""
        seen, out = set(), []
        for eng in (self.serving, self.finetune):
            obs = getattr(eng, "_obs", None)
            if eng is None or obs is None or id(obs) in seen:
                continue
            seen.add(id(obs))
            if client is None:
                out.extend(obs.drain_events(kind=kind))
            else:
                out.extend(obs.drain_events(client=client, kind=kind))
        out.sort(key=lambda e: e.seq)
        return out

    def run(self):
        """Drive both workloads to completion against the shared base.
        Returns (finished inference Requests, finished FinetuneJobs)."""
        while self.tick():
            pass
        done_reqs = self.serving.drain_done() if self.serving else []
        done_jobs = []
        if self.finetune is not None:
            done_jobs, self.finetune.finished = self.finetune.finished, []
        return done_reqs, done_jobs

    # ------------------------------------------------------------------
    # engine-level crash recovery (docs/robustness.md)
    # ------------------------------------------------------------------
    def checkpoint(self, directory) -> int:
        """Atomically write BOTH engines' whole-engine snapshots plus the
        wrapper's own stats as one CRC-framed blob
        (``checkpoint.save_engine_state``); returns the sequence number.
        Kill → ``restore`` into freshly constructed engines resumes every
        tenant bitwise (tests/test_faults.py)."""
        import re
        from repro.checkpoint import save_engine_state
        state = {
            "serving": (None if self.serving is None
                        else self.serving.engine_state()),
            "finetune": (None if self.finetune is None
                         else self.finetune.engine_state()),
            "stats": dict(self.stats),
        }
        path = save_engine_state(directory, state)
        return int(re.search(r"engine_(\d+)\.ckpt$", path).group(1))

    def restore(self, directory) -> int:
        """Load the newest VALID engine snapshot (corrupt files are skipped
        — last-good-wins) into this freshly constructed service; returns
        the sequence number restored."""
        from repro.checkpoint import load_engine_state
        seq, state = load_engine_state(directory)
        if state["serving"] is not None:
            if self.serving is None:
                raise RuntimeError("checkpoint holds serving state but no "
                                   "serving engine is attached")
            self.serving.load_engine_state(state["serving"])
        if state["finetune"] is not None:
            if self.finetune is None:
                raise RuntimeError("checkpoint holds finetune state but no "
                                   "finetune engine is attached")
            self.finetune.load_engine_state(state["finetune"])
        self.stats.update(state["stats"])
        return seq

"""Placement router: the service provider's admission + placement logic
(paper §3.3/§3.4).

Given a request (context length, batch, latency sensitivity) and the fleet
(accelerator slots with free HBM, CPU hosts), choose the §3.4 placement:

  * ``gpu``          — client co-located with the base executor (fastest,
                       needs cache + runtime state to fit free HBM)
  * ``gpu_offload``  — cache on host, compute on accelerator (mid contexts)
  * ``hetero``       — client on CPU (huge contexts; constant PCIe traffic)

and an accelerator slot, using the analytic cost model in
``serving.kvcache``. This is the piece the paper assigns to the provider:
"they only need to provision the base executor resources ... the per-token
resource requirement remains constant irrespective of the client-side
configurations" — client placement is decided per request here.

``serving.engine`` uses this as admission control: a request is admitted
only when ``route()`` finds (and commits) a placement; the engine calls
``release()`` when the request's slots free, so queued requests take the
capacity the moment it returns (continuous-batching backpressure).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

from repro.config import ModelConfig
from repro.common.hardware import Chip, chip_for
from repro.serving.kvcache import cache_bytes, decode_token_cost


@dataclasses.dataclass
class Slot:
    """One accelerator's client-side capacity (base executor excluded)."""
    slot_id: int
    free_hbm: float

    def fits(self, nbytes: float) -> bool:
        return nbytes <= self.free_hbm


@dataclasses.dataclass
class Placement:
    slot_id: Optional[int]        # None -> CPU host
    mode: str                     # gpu | gpu_offload | hetero | train
    est_s_per_token: float
    cache_bytes: int


class PlacementRouter:
    """Routes client sessions onto a fleet of accelerator slots + CPU hosts."""

    def __init__(self, cfg: ModelConfig, slots: List[Slot],
                 *, chip: Optional[Chip] = None,
                 host_free_bytes: float = 400e9):
        """``chip`` prices the placements; None takes the table entry of
        the local device (``common.hardware.chip_for``), which is an error
        on a device the table does not know."""
        self.cfg = cfg
        self.chip = chip or chip_for()
        self.slots = {s.slot_id: s for s in slots}
        self.host_free = host_free_bytes
        # conservation ledger (docs/robustness.md): initial capacities plus
        # the identity list of outstanding placements. commit/release keep
        # it in sync; conservation_errors() recomputes free capacity from
        # scratch and reports any drift (a leaked or double-released charge).
        self._initial = {s.slot_id: s.free_hbm for s in slots}
        self._host_initial = host_free_bytes
        self._committed: List[Placement] = []

    def route(self, context_len: int, batch: int = 1,
              *, latency_sensitive: bool = True, alloc_tokens: int = 0,
              quant: bool = False) -> Placement:
        """Pick the cheapest placement that fits; latency-sensitive requests
        refuse the CPU unless nothing else fits.

        ``context_len`` drives the latency estimates (tokens actually
        attended); ``alloc_tokens`` drives the HBM charge (tokens the cache
        layout actually pins — a dense engine passes its ``max_seq`` slot
        depth, a paged engine its context already rounded up to whole
        pages). 0 falls back to ``context_len``. ``quant`` prices the int8
        KV layout."""
        # cache_bytes already multiplies by `batch` — `need` is the whole
        # session's footprint, and is what commit()/release() account with.
        need = cache_bytes(self.cfg, alloc_tokens or context_len, batch,
                           quant=quant)
        candidates = []

        gpu = decode_token_cost(self.cfg, context_len, placement="gpu",
                                chip=self.chip)
        off = decode_token_cost(self.cfg, context_len,
                                placement="gpu_offload", chip=self.chip)
        het = decode_token_cost(self.cfg, context_len, placement="hetero",
                                chip=self.chip)

        for s in self.slots.values():
            if gpu.total != float("inf") and s.fits(need):
                candidates.append(Placement(s.slot_id, "gpu",
                                            gpu.total * batch, need))
            # offload only needs working-set HBM (~1 layer of cache)
            if self.host_free >= need and s.fits(need / self.cfg.n_layers):
                candidates.append(Placement(s.slot_id, "gpu_offload",
                                            off.total * batch, need))
        if self.host_free >= need:
            pen = 1.0 if not latency_sensitive else 1.5   # soft CPU aversion
            candidates.append(Placement(None, "hetero",
                                        het.total * batch * pen, need))
        if not candidates:
            raise RuntimeError(
                f"no placement fits {need/1e9:.1f} GB cache "
                f"(context {context_len} × batch {batch})")
        best = min(candidates, key=lambda p: p.est_s_per_token)
        # undo the latency penalty in the reported estimate BEFORE commit:
        # the ledger tracks placements by identity, so the object we commit
        # must be the object the caller later release()s
        if best.mode == "hetero" and latency_sensitive:
            best = dataclasses.replace(best,
                                       est_s_per_token=best.est_s_per_token / 1.5)
        self.commit(best)
        return best

    def route_train(self, nbytes: float, *,
                    latency_sensitive: bool = False) -> Placement:
        """Place one FINE-TUNING job's client-side state: adapter params +
        AdamW moments + activation working set (``training.job_hbm_bytes``).
        Training state is touched every step for the job's whole lifetime,
        so only co-located (accelerator-resident) placements are considered
        — there is no offload tier for optimizer state. Commits the
        capacity; the FinetuneEngine releases it when the job retires.
        ``latency_sensitive`` is accepted for signature symmetry with
        ``route`` (training placements are always co-located)."""
        del latency_sensitive
        for s in self.slots.values():
            if s.fits(nbytes):
                p = Placement(s.slot_id, "train", 0.0, int(nbytes))
                self.commit(p)
                return p
        raise RuntimeError(
            f"no accelerator slot fits {nbytes / 1e9:.2f} GB of training "
            f"state (adapter + optimizer + activations)")

    def route_bank(self, nbytes: float) -> Placement:
        """Charge one SERVING bank's resident client-side weights: the
        client-stacked adapter trees a mixed-method engine keeps on the
        accelerator for its whole lifetime (per-bank HBM accounting of the
        engine's bank registry). Like training state there is no offload
        tier — adapters are read every decode tick. The engine releases the
        charge via ``ServingEngine.release_banks()``."""
        for s in self.slots.values():
            if s.fits(nbytes):
                p = Placement(s.slot_id, "bank", 0.0, int(nbytes))
                self.commit(p)
                return p
        raise RuntimeError(
            f"no accelerator slot fits {nbytes / 1e9:.3f} GB of serving-bank "
            f"adapter weights")

    def commit(self, p: Placement):
        if p.slot_id is not None and p.mode in ("gpu", "train", "bank"):
            self.slots[p.slot_id].free_hbm -= p.cache_bytes
        elif p.slot_id is not None:
            self.slots[p.slot_id].free_hbm -= p.cache_bytes / self.cfg.n_layers
            self.host_free -= p.cache_bytes
        else:
            self.host_free -= p.cache_bytes
        self._committed.append(p)

    def release(self, p: Placement):
        # identity scan, not list.remove: Placement is a value-comparing
        # dataclass, and two tenants can hold field-equal placements
        for i, q in enumerate(self._committed):
            if q is p:
                del self._committed[i]
                break
        else:
            raise RuntimeError(
                f"release of a placement that was never committed (or was "
                f"already released): {p}")
        if p.slot_id is not None and p.mode in ("gpu", "train", "bank"):
            self.slots[p.slot_id].free_hbm += p.cache_bytes
        elif p.slot_id is not None:
            self.slots[p.slot_id].free_hbm += p.cache_bytes / self.cfg.n_layers
            self.host_free += p.cache_bytes
        else:
            self.host_free += p.cache_bytes

    def utilization(self) -> dict:
        """Telemetry snapshot (docs/observability.md): live vs initial
        capacity per slot plus the outstanding-placement ledger. The
        engines fold this into ``router_*`` gauges at admit/retire when an
        ``Obs`` is attached; pure host reads, no device traffic."""
        return {
            "slots": {sid: {"free_hbm": s.free_hbm,
                            "initial_hbm": self._initial.get(sid, s.free_hbm)}
                      for sid, s in self.slots.items()},
            "host_free": self.host_free,
            "host_initial": self._host_initial,
            "placements": len(self._committed),
            "committed_bytes": sum(p.cache_bytes for p in self._committed),
        }

    def conservation_errors(self) -> List[str]:
        """Recompute every capacity from the initial snapshot minus the
        outstanding placements; any drift from the live counters means a
        charge leaked (admission failed after commit) or was double-
        released. Empty list == conserved."""
        errs = []
        want_slot = dict(self._initial)
        want_host = self._host_initial
        for p in self._committed:
            if p.slot_id is not None and p.mode in ("gpu", "train", "bank"):
                want_slot[p.slot_id] -= p.cache_bytes
            elif p.slot_id is not None:
                want_slot[p.slot_id] -= p.cache_bytes / self.cfg.n_layers
                want_host -= p.cache_bytes
            else:
                want_host -= p.cache_bytes
        for sid, s in self.slots.items():
            if sid not in want_slot:        # slot added after construction
                continue
            if abs(s.free_hbm - want_slot[sid]) > 1.0:   # bytes; fp slack
                errs.append(
                    f"slot {sid}: free_hbm {s.free_hbm:.0f} != ledger "
                    f"{want_slot[sid]:.0f} (leaked/double-released charge)")
        if abs(self.host_free - want_host) > 1.0:
            errs.append(f"host: free {self.host_free:.0f} != ledger "
                        f"{want_host:.0f}")
        return errs

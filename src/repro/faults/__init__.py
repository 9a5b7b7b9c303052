"""Fault containment for multi-tenant engines (docs/robustness.md):
per-tenant health state machine, deterministic fault injection, and
conservation audits. The chaos sweep lives in ``repro.faults.chaos``
(``python -m repro.faults.chaos``)."""
from repro.faults.health import (AdmissionStall, FatalFault, HealthPolicy,
                                 HealthRecord,
                                 HealthState, TransientFault, classify)
from repro.faults.plan import (KINDS, AllocHook, AllocationFault, FaultEvent,
                               FaultPlan, FaultyStream, NonFiniteFault,
                               StreamError, StreamExhausted, corrupt_flip,
                               corrupt_truncate)
from repro.faults.audit import (check_conservation, finetune_conservation,
                                serving_conservation)

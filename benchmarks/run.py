"""Benchmark driver: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME] [--smoke]
                                          [--json PATH]

Emits CSV lines (bench,key=value,...) and writes experiments/bench/*.json.

``--smoke`` is the CI guard against benchmark rot: it imports EVERY bench
module (so stale imports/APIs fail loudly) and runs a few real ticks of
bench_multiclient on tiny configs — the serving comparison, the
paged-admission-at-fixed-HBM section, and the compacted-decode occupancy
sweep.

``--json PATH`` persists the serving-side sections (continuous-batching
tok/s, the telemetry-backed ``serving_latency`` tail-latency section,
paged admission counts, compacted-decode speedups) as one combined
JSON document, so the bench trajectory is machine-readable across PRs —
the CI bench-smoke job writes ``BENCH_serving.json`` from the same run,
plus the raw telemetry behind the latency section as ``BENCH_obs.jsonl``
and ``BENCH_obs.prom`` (validated by ``python -m repro.obs --check``).
The TRAINING sections (fine-tuning-as-a-service: shared-base vs dedicated
replicas HBM/step-s, heterogeneous bank mix) are persisted alongside it as
``BENCH_training.json`` in the same directory.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import time
import traceback

BENCHES = [
    ("table2_adapter_configs", "benchmarks.bench_adapter_configs"),
    ("fig9_10_memory", "benchmarks.bench_memory"),
    ("fig11_12_multiclient", "benchmarks.bench_multiclient"),
    ("sec5_finetune_service", "benchmarks.bench_finetune_service"),
    ("fig18_19_heterogeneous", "benchmarks.bench_heterogeneous"),
    ("fig21_privacy", "benchmarks.bench_privacy"),
    ("fig22_23_mixed", "benchmarks.bench_mixed"),
    ("table4_5_batching", "benchmarks.bench_batching"),
    ("kernels", "benchmarks.bench_kernels"),
    ("ablations", "benchmarks.bench_ablations"),
]

# sections whose rows carry the serving trajectory (tok/s, admission and
# compaction counts) persisted by --json
SERVING_SECTIONS = (
    "sec37_serving_continuous_batching",
    "serving_latency",
    "paged_admission_fixed_hbm",
    "compact_decode_sparse_occupancy",
    "mixed_method_serving",
    "sharded_serving",
)

# training trajectory sections (--json writes them to BENCH_training.json)
TRAINING_SECTIONS = (
    "finetune_service_shared_base",
    "finetune_service_bank_mix",
)

# row-schema key -> section name, across both documents
_SCHEMA_OF = {
    "engine": "sec37_serving_continuous_batching",
    "latency": "serving_latency",
    "layout": "paged_admission_fixed_hbm",
    "occupancy": "compact_decode_sparse_occupancy",
    "mix": "mixed_method_serving",
    "workload": "finetune_service_shared_base",
    "bankmix": "finetune_service_bank_mix",
    "sharded": "sharded_serving",
}


def _write_sections_json(path: str, rows: list, section_names, label: str):
    """Split a flat row list back into its sections by schema and persist."""
    import jax

    sections = {name: [] for name in section_names}
    for row in rows:
        for key, name in _SCHEMA_OF.items():
            if key in row and name in sections:
                sections[name].append(row)
                break
    doc = {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "unix_time": int(time.time()),
        "sections": sections,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, default=str)
    print(f"{label} bench trajectory written to {path}")


def _write_serving_json(path: str, rows: list):
    _write_sections_json(path, rows, SERVING_SECTIONS, "serving")


def _training_json_path(serving_path: str) -> str:
    return os.path.join(os.path.dirname(serving_path) or ".",
                        "BENCH_training.json")


def _write_training_json(serving_path: str, rows: list):
    _write_sections_json(_training_json_path(serving_path), rows,
                         TRAINING_SECTIONS, "training")


def _write_obs_exports(serving_path: str):
    """Persist the latency section's raw telemetry next to the --json doc:
    BENCH_obs.jsonl (full metric+event dump) and BENCH_obs.prom (Prometheus
    text exposition) — the artifacts the CI bench-smoke job uploads and
    validates with ``python -m repro.obs --check``."""
    import benchmarks.bench_multiclient as bmc
    obs = bmc.LAST_LATENCY_OBS
    if obs is None:
        return
    from repro.obs import export
    out_dir = os.path.dirname(serving_path) or "."
    jl = os.path.join(out_dir, "BENCH_obs.jsonl")
    pm = os.path.join(out_dir, "BENCH_obs.prom")
    export.write_jsonl(jl, obs)
    export.write_prometheus(pm, obs)
    print(f"telemetry exports written to {jl} and {pm}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller models / fewer points")
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: import every bench, run bench_multiclient "
                         "serving + paged-admission + compaction sections on "
                         "tiny configs")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="persist the serving/paged/compaction sections' rows "
                         "(tok/s, admission counts) as one JSON document")
    args = ap.parse_args()
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()

    import importlib
    if args.smoke:
        for name, modname in BENCHES:
            importlib.import_module(modname)       # rot check: must import
        print(f"imported {len(BENCHES)} bench modules OK")
        t0 = time.time()
        rows = importlib.import_module("benchmarks.bench_multiclient").run_smoke()
        train_rows = importlib.import_module(
            "benchmarks.bench_finetune_service").run_smoke()
        # Bucket-coverage smoke (docs/invariants.md pass 3): a short REAL
        # engine workload — serving + live bank admission + finetune churn —
        # under the trace-count guard, so a hot-path recompile outside the
        # declared jit bucket sets fails the smoke job. Deliberately NOT
        # wrapped around the timed sections above: the guard's per-dispatch
        # cache probing is measurable at tiny-config tick times and would
        # distort the tok/s ratios the floors assert on.
        from repro.analysis.runner import run_buckets
        res = run_buckets()
        print(f"trace guard: {res.checked}")
        if not res.ok:
            raise SystemExit("bench smoke hit hot-path trace violations:\n"
                             + "\n".join(str(v) for v in res.violations))
        print(f"bench smoke complete in {time.time() - t0:.1f}s")
        if args.json:
            # sharded_serving rows come from BOTH benches (serving identity
            # from bench_multiclient, finetune identity from
            # bench_finetune_service) — route the combined list so all of
            # them land in the serving document's section
            _write_serving_json(args.json, rows + train_rows)
            _write_training_json(args.json, train_rows)
            _write_obs_exports(args.json)
        return

    failures = []
    serving_rows = []
    training_rows = []
    for name, modname in BENCHES:
        if args.only and args.only not in name:
            continue
        print(f"\n=== {name} ({modname}) ===")
        t0 = time.time()
        try:
            mod = importlib.import_module(modname)
            rows = mod.run(quick=args.quick)
            if name == "fig11_12_multiclient" and rows:
                serving_rows = rows
            if name == "sec5_finetune_service" and rows:
                training_rows = rows
            print(f"=== {name}: done in {time.time() - t0:.1f}s ===")
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if args.json and serving_rows:
        _write_serving_json(args.json, serving_rows + training_rows)
        _write_obs_exports(args.json)
    if args.json and training_rows:
        _write_training_json(args.json, training_rows)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    print("\nall benchmarks complete")


if __name__ == "__main__":
    main()

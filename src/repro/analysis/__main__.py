import os
os.environ["JAX_PLATFORMS"] = "cpu"
if "XLA_FLAGS" not in os.environ:               # noqa: E402 — see below
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
"""Invariant-checker CLI: ``python -m repro.analysis --all [--json]``.

Runs every pass over the standard hot-path targets (see ``targets.py``)
and exits nonzero if any error-severity violation survives. The lines
above MUST stay the first statements: jax fixes its platform and device
count at first initialization, and the collective audit (``--mesh``,
included in ``--all``) compiles the steps under a (data=2, model=2) mesh
of host devices. The CPU is pinned so that the checker never takes an
accelerator the host may have.

  --static     donation / poolcopy / moe-remat / frozen-base passes only
  --buckets    engine workload under the trace-count guard only
  --isolation  differential client/row isolation probes only
  --mesh       collective audit under the 2x2 host mesh only
  --all        everything (the CI gate)
  --json       machine-readable report on stdout
  --out PATH   also write the JSON report to PATH
"""
import argparse
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.analysis")
    ap.add_argument("--all", action="store_true", help="run every pass")
    ap.add_argument("--static", action="store_true")
    ap.add_argument("--buckets", action="store_true")
    ap.add_argument("--isolation", action="store_true")
    ap.add_argument("--mesh", action="store_true")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--out", default=None, help="write JSON report here")
    args = ap.parse_args(argv)
    if not (args.all or args.static or args.buckets or args.isolation
            or args.mesh):
        args.all = True

    from repro.analysis import runner
    from repro.analysis.report import render_report, report_payload
    from repro.analysis.targets import all_targets

    results = []
    targets = all_targets()
    if args.all or args.static:
        results.extend(runner.run_static(targets))
    if args.all or args.buckets:
        results.append(runner.run_buckets())
    if args.all or args.mesh:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        results.extend(runner.run_collectives(targets, mesh=mesh))
    if args.all or args.isolation:
        results.extend(runner.run_isolation(targets))

    payload = report_payload(results)
    print(render_report(results, as_json=args.json))
    if args.out:
        import json
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
    return 0 if payload["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

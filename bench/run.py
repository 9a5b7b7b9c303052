#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

  python3 bench/run.py --workload qwen3-4b.decode-saturated --seed 7 \\
      --seconds 50 --trace 0

The cell is the ``workloads`` entry of ``BENCHMARK.json`` with that name;
its configuration, traffic mix and per-layer metric readers are found by
name under ``bench/`` (see ``bench/harness.py``). Inputs and weights come
from ``--seed``. With ``--trace 0`` the last line on standard output is the
result with the cell's end-to-end metrics; with ``--trace 1`` it carries
the per-layer metrics, read with the engines' spans on and a profiler trace
of a few seconds of the window. Without a TPU, or with fewer chips than the
cell asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, roots=None, require_tpu: bool = True,
         t_start: float = None) -> int:
    args = parse(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    import repro  # noqa: F401  the system under test; absent -> no result

    from bench import harness
    reg = harness.Registry(roots or [ROOT])
    cell = reg.cell(args.workload)
    devices, why = harness.device_check(cell["workload"]["chips"],
                                        require_tpu)
    if devices is None:
        print(f"bench/run.py: {why}; no run was made", file=sys.stderr)
        return 2
    if require_tpu:
        harness.enable_compile_cache(ROOT)
    run = harness.Run(args, cell, reg, T_START if t_start is None else
                      t_start, devices, ROOT)
    reg.kind(cell["traffic"]["kind"]).run(run)
    harness.emit(run)
    return 0


if __name__ == "__main__":
    sys.exit(main())

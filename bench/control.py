#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell, in
one process: the program's number on each of many seeds (the lower
reading), and the same number for the control, the plain reference computed
in the precision just below the configuration's (the upper reading), plus
the faults a fine-tuning cell is held against.

  python3 bench/control.py --workload qwen3-4b.decode-saturated --seconds 8 \\
      --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 1,2,3

Serving: each seed runs a short window at the cell's own load through the
same set-up and driver as a benchmark run, then the reference scores the
same sample a run scores; on a control seed it also scores the tokens that
the lower-precision reference puts first. Fine-tuning: each seed drives the
engine through the checked steps; on a control seed the lower-precision
reference, the reference on half of each batch (where a job has two rows or
more) and the reference whose multi-job banks train every job on the first
job's rows are read against the reference too. One JSON line per seed. Not
run by the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def serving(run, seed, control, lowp):
    from bench.traffic import open_loop as ol
    svc, obs, compiles, _ = ol.setup(run)
    w = ol.window(run, svc, obs, compiles, run.traffic, seed, run.seconds,
                  time.perf_counter())
    done = sum(1 for r in w["measured"] if r.finish_tick is not None)
    del svc, obs
    gc.collect()
    got = ol.readings(run, w["recs"], w["kept"],
                      lowp=lowp if control else "")
    return dict(got, finished=done, due=len(w["measured"]))


def finetune(run, seed, control, lowp):
    from bench.traffic import finetune_jobs as fj
    svc, jobs, adapters, _ = fj.build(run, None)
    seen = fj.first_steps(run, svc, jobs, adapters)
    del svc, jobs, adapters
    gc.collect()
    ref = fj.follow(run)
    out = fj.worst(seen, ref)
    if control:
        for k, v in fj.worst(fj.as_seen(fj.follow(run, lowp=lowp)),
                             ref).items():
            out["control." + k] = v
        dropped = fj.follow(run, data_of=fj.bank_row_dropped(run.traffic))
        for k, v in fj.worst(fj.as_seen(dropped), ref).items():
            out["bank_row_dropped." + k] = v
        rows = run.traffic["rows"]
        if rows >= 2:
            half = fj.follow(run, rows=rows // 2)
            for k, v in fj.worst(fj.as_seen(half), ref).items():
                out["half_batch." + k] = v
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness
    from bench.reference import CONTROL

    reg = harness.Registry([ROOT])
    cell = reg.cell(args.workload)
    devices, why = harness.device_check(cell["workload"]["chips"])
    if devices is None:
        print(f"bench/control.py: {why}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    lowp = CONTROL[cell["config"]["model"]["dtype"]]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    kind = cell["traffic"]["kind"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        ns = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0)
        run = harness.Run(ns, cell, reg, time.perf_counter(), devices, ROOT)
        t = time.perf_counter()
        fn = serving if kind == "open_loop" else finetune
        out = fn(run, seed, seed in ctl, lowp)
        out.update(seed=seed, seconds=time.perf_counter() - t,
                   lowp=lowp if seed in ctl else "")
        print(json.dumps(out), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

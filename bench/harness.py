"""What every cell's run shares: finding files by name, the chip check,
the compile cache and compile count, the profiler window, and the result
line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``   the model configuration as run;
- ``bench/traffic/<mix>.json``      the mix's parameters; its ``kind``
  names the driver module ``bench/traffic/<kind>.py``;
- ``bench/metrics/<metric>.py``     one reader per per-layer metric, with
  ``read(view) -> float | None``;
- ``bench/arch/<arch>.py``          the model family that the configuration's
  ``model["arch"]`` names: its base leaves, adapter target widths, the
  reference's layer, the FLOPs a token requires and the published widths
  it checks (``bench/arch/dense.py`` lists what a family module gives).
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Registry:
    """Files found by name under a list of roots, the first root first.
    ``BENCHMARK.json`` is read from the first root."""

    def __init__(self, roots=None):
        self.roots = list(roots or [ROOT])
        with open(os.path.join(self.roots[0], "BENCHMARK.json")) as f:
            self.bench = json.load(f)

    def _find(self, *parts) -> str:
        for r in self.roots:
            p = os.path.join(r, "bench", *parts)
            if os.path.exists(p):
                return p
        raise FileNotFoundError("bench/" + "/".join(parts))

    def json(self, *parts) -> dict:
        with open(self._find(*parts)) as f:
            return json.load(f)

    def module(self, *parts):
        path = self._find(*parts)
        name = "bench_" + "_".join(parts).replace(".", "_").replace("-", "_")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def cell(self, workload: str) -> dict:
        """The workload entry joined with its configuration, its mix and the
        metrics it reports."""
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        w = cells[workload]

        def mine(m):
            return workload in m.get("workloads", [workload])

        return {"workload": w,
                "config": self.json("configs", w["config"] + ".json"),
                "traffic": self.json("traffic", w["traffic"] + ".json"),
                "end_to_end": [m for m in self.bench["end_to_end"] if mine(m)],
                "per_layer": [m for m in self.bench["per_layer"] if mine(m)]}

    def kind(self, kind: str):
        return self.module("traffic", kind + ".py")

    def reader(self, metric: str):
        return self.module("metrics", metric + ".py")

    def arch(self, name: str):
        return self.module("arch", name + ".py")


def family(cfg: dict, given=None):
    """The family module of a model configuration: ``given`` where the
    caller holds it (a run's, or a reader's ``view.family``), else the
    benchmark's own ``bench/arch/<arch>.py`` for its ``arch``."""
    return given or Registry().arch(cfg["arch"])


def device_check(chips: int, require_tpu: bool = True):
    """The devices a cell may use, or a reason to refuse the run."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        return None, (f"needs a TPU; JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        return None, f"needs {chips} chips; JAX found {len(devs)}"
    return devs[:chips], None


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where it is
    set, else ``.jax_cache`` at the root of the checkout (a fixed path: the
    path is part of what the cache matches). Every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCount:
    """XLA backend compiles (count and seconds) since construction."""

    def __init__(self):
        import jax
        self.n, self.secs = 0, 0.0

        def on_event(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.secs += secs

        jax.monitoring.register_event_duration_secs_listener(on_event)


class GcPauses:
    """The garbage collector's pauses from construction to ``stop``: a
    diagnostic for stalls of the host loop, printed on an earlier line."""

    def __init__(self):
        import gc
        self.n, self.total, self.most, self._t = 0, 0.0, 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.n, self.total, self.most = self.n + 1, self.total + d, \
                max(self.most, d)

    def stop(self) -> dict:
        import gc
        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)
        return {"collections": self.n, "total_s": self.total,
                "longest_s": self.most}


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    best = 0
    for d in devices:
        st = d.memory_stats() or {}
        best = max(best, int(st.get("peak_bytes_in_use", 0)))
    return best


def in_use_bytes(devices) -> int:
    """Bytes in use now on the fullest chip (0 where not reported)."""
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devices)


def peaks(run) -> dict:
    """The chip's peaks from ``bench/peaks.json``; a device kind the table
    does not hold is an error, never a default."""
    table = run.registry.json("peaks.json")
    kind = run.devices[0].device_kind
    if kind not in table["chips"]:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       "bench/peaks.json")
    return table["chips"][kind]


class Profile:
    """The traced window: ``start``/``stop`` at tick boundaries, then the
    reduction of its trace. ``t0`` is taken once the profiler runs and
    ``t1`` before it stops, so the window leaves out the seconds the
    profiler takes to start and stop, when no tick runs; the drivers take
    their snapshots of the traced window at the same two points."""

    def __init__(self, path: str):
        self.path = path
        self.t0 = self.t1 = None

    def start(self):
        import shutil

        import jax
        shutil.rmtree(self.path, ignore_errors=True)
        os.makedirs(self.path, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans only, no Python calls
        jax.profiler.start_trace(self.path, profiler_options=opts)
        self.t0 = time.perf_counter()

    def stop(self):
        import jax
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self) -> dict:
        import shutil

        from bench import trace
        events = trace.load(self.path)
        red = trace.reduce(events)
        shutil.rmtree(self.path, ignore_errors=True)
        if red:
            red["host_window_s"] = self.t1 - self.t0
        return red


class Run:
    """One run of one cell: its inputs, and what the driver fills in."""

    def __init__(self, args, cell: dict, registry: Registry, t_start: float,
                 devices, root: str):
        self.args, self.cell, self.registry = args, cell, registry
        self.t_start, self.devices, self.root = t_start, devices, root
        self.seed, self.seconds, self.traced = args.seed, args.seconds, \
            bool(args.trace)
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.family = registry.arch(self.config["model"]["arch"])
        self.metrics = {}            # end-to-end values by name
        self.view = None             # what per-layer readers read
        self.correct = False
        self.attempted = self.failed = 0
        self.checks = {}             # name -> (value, limit)
        self.notes = {}              # earlier-line facts (lateness, compiles)
        self.memory_peak = 0
        self.memory = {}             # what the window held, beside the peak
        self.trace = {}

    def check(self, name: str, value: float, limit: float) -> bool:
        """A compared number beside its limit; passes when value <= limit."""
        self.checks[name] = (value, limit)
        return value <= limit

    def note(self, key, value):
        self.notes[key] = value
        print(f"{key}: {json.dumps(value, default=_num)}", file=sys.stderr,
              flush=True)


def _num(x):
    return float(x)


def result(run: Run) -> dict:
    """The result line. End-to-end metrics untraced, per-layer traced."""
    dev = run.devices[0]
    out = {"correct": bool(run.correct), "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": {},
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(run.devices),
                      "memory_peak_bytes": int(run.memory_peak)}}
    if run.traced:
        for m in run.cell["per_layer"]:
            v = run.registry.reader(m["name"]).read(run.view)
            if v is not None and math.isfinite(v):
                out["metrics"][m["name"]] = {"value": float(v),
                                             "unit": m["unit"]}
        if run.trace:
            out["device"]["busy_s"] = run.trace["busy_s"]
            out["device"]["window_s"] = run.trace["host_window_s"]
            from bench import trace
            out["breakdown"] = trace.breakdown(run.trace)
    else:
        for m in run.cell["end_to_end"]:
            if m["name"] in run.metrics:
                out["metrics"][m["name"]] = {"value": float(
                    run.metrics[m["name"]]), "unit": m["unit"]}
    if run.memory:
        out["memory"] = {k: int(v) for k, v in run.memory.items()}
    out["checks"] = {k: {"value": float(v), "limit": float(l)}
                     for k, (v, l) in run.checks.items()}
    return out


def emit(run: Run):
    """Each compared number beside its limit as the last lines on standard
    error, then the result as the last line on standard output."""
    res = result(run)
    for k, c in res["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return res

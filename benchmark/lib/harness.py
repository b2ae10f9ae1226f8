"""The command behind `benchmark/run.py`: finds the cell's files by the
names in BENCHMARK.json, places the caches, refuses to run without the
chips, hands the cell to its runner and prints the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own (see benchmark/README.md);
this module knows none of them by name.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import time

from . import traffic as traffic_lib

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
TRACE_SECONDS = 5.0     # the traced part of a --trace 1 window: its end


def say(*parts):
    print(*parts, flush=True)


class Refused(Exception):
    """The run cannot be a measurement (no chip, bad arguments)."""


def load_cell(workload: str) -> dict:
    """The cell's entry, configuration and traffic, by name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json "
                      f"(has: {[w['name'] for w in bench['workloads']]})")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = traffic_lib.load(
        traffic_lib.find(BENCH_DIR, "traffic", cell["traffic"]))
    return {"bench": bench, "cell": cell, "config": config,
            "traffic": traffic}


def reports(metric: dict, cell_name: str) -> bool:
    """Whether the cell reports `metric` (an entry of BENCHMARK.json): its
    `workloads` list says so. Without one the metric is every cell's, and
    a per-layer reader that finds nothing to read there returns None."""
    return cell_name in metric.get("workloads", (cell_name,))


def place_caches() -> tuple[str, str]:
    """The compile cache and the kernel gate's cache, before jax starts.

    The compile cache is where `JAX_COMPILATION_CACHE_DIR` says, else at
    the fixed path the program uses (utils/compile_cache.py: .jax_cache in
    the checkout). The gate's cache is a file in that directory unless
    `PADDLE_TPU_AUTOBENCH_CACHE` is already set: a cell's first run in a
    checkout decides each kernel-gate key once, and every later run adopts
    the decisions, traces the same programs and hits the compile cache.
    """
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = os.path.join(ROOT, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.makedirs(cache, exist_ok=True)
    gate = os.environ.setdefault(
        "PADDLE_TPU_AUTOBENCH_CACHE",
        os.path.join(cache, "autobench_gate.json"))
    # small programs too: a warm run then compiles nothing at all
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return cache, gate


class Context:
    """What a runner gets."""

    def __init__(self, cell, args, t_start, rehearsal=False, control=None):
        self.cell = cell["cell"]
        self.config = cell["config"]
        self.traffic = cell["traffic"]
        self.bench = cell["bench"]
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(int(args.trace))
        self.t_start = t_start
        self.rehearsal = rehearsal
        # tools/probe.py only: also compute the lower-precision control
        self.control = control
        self.cache_events = {"hits": 0, "misses": 0}
        self.gc_pauses = 0
        self.lowerings = 0          # programs lowered so far (any jit)
        self.checks = []            # [name, value, limit, ok]
        self.say = say
        self.control_readings = {}  # what the control would be judged by

    # -- set-up clock ------------------------------------------------------
    def setup_seconds(self, now=None) -> float:
        return (now if now is not None else time.perf_counter()) \
            - self.t_start

    # -- correctness -------------------------------------------------------
    def check(self, name: str, value, limit, ok=None):
        """One number compared, printed beside its limit in every run."""
        if ok is None:
            ok = value is not None and math.isfinite(value) \
                and value <= limit
        self.checks.append([name, value, limit, bool(ok)])
        say(f"check {name}: {value!r} (limit {limit!r}) "
            f"{'ok' if ok else 'FAILED'}")
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c[3] for c in self.checks)

    def reference(self):
        return importlib.import_module(
            f"benchmark.reference.{self.config['reference']}")

    # -- the clean window --------------------------------------------------
    def freeze_gc(self):
        """After warm-up: collect once, freeze the survivors, and count
        every later pause of the collector."""
        gc.collect()
        gc.freeze()

        def on_gc(phase, _info):
            if phase == "start":
                self.gc_pauses += 1
        gc.callbacks.append(on_gc)

    def release(self):
        """After the window: let the collector take what the program held
        in reference cycles (frozen objects are never collected), so that
        the reference finds the device's memory free."""
        gc.unfreeze()
        gc.collect()

    # -- trace -------------------------------------------------------------
    def start_trace(self):
        import jax
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)

    def stop_trace(self):
        """Stop the profiler and return the reduced trace."""
        import jax
        from . import trace as trace_lib
        jax.profiler.stop_trace()
        try:
            raw = trace_lib.load_xplane(
                trace_lib.find_xplane(self._trace_dir),
                keep_host=lambda n: n.startswith("bench."))
        finally:
            keep = os.environ.get("BENCH_KEEP_TRACE")
            if keep:
                os.makedirs(keep, exist_ok=True)
                with open(os.path.join(keep, "trace.json"), "w") as f:
                    json.dump(raw, f)
            shutil.rmtree(self._trace_dir, ignore_errors=True)
        return trace_lib.Reduced(raw)


def _merge(dst: dict, src: dict):
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _merge(dst[k], v)
        else:
            dst[k] = v


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(jax, chips: int) -> int:
    peak = 0
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def gate_decisions() -> dict:
    """The kernel gate's decisions of this process: key -> winner."""
    from paddle_tpu.ops import autobench
    return {str(k): v for k, v in autobench.decisions().items()}


def layer_metrics(ctx: Context, run: dict) -> dict:
    """Every per-layer metric of BENCHMARK.json that this cell reports,
    read by the metric's own reader (benchmark/layer_metrics/<name>.json
    names it). A reader that finds nothing returns None and the metric is
    left out of the line. A reading above the file's `max` (a share of a
    peak or of a whole cannot pass 100) fails the run."""
    out = {}
    for m in ctx.bench["per_layer"]:
        if not reports(m, ctx.cell["name"]):
            continue
        with open(traffic_lib.find(BENCH_DIR, "layer_metrics",
                                   m["name"])) as f:
            spec = json.load(f)
        mod, fn = spec["reader"].split(":")
        reader = getattr(importlib.import_module(
            f"benchmark.readers.{mod}"), fn)
        try:
            value = reader(run, **spec.get("args", {}))
        except KeyError:
            if not ctx.rehearsal:   # a CPU has no published peak
                raise
            continue
        if value is None:
            continue
        value = float(value)
        if "max" in spec and value > spec["max"]:
            # the count or the time is at fault: say so, fail the run
            ctx.check(f"{m['name']} within its maximum", value, spec["max"])
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv, t_start: float, rehearsal: bool = False, control=None,
         overrides=None) -> int:
    """One run of one cell. `control` and `overrides` are for the tools
    (tools/probe.py): the benchmark's command passes neither."""
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
    except Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    chips = int(cell["cell"]["chips"])
    if rehearsal:
        # the same code at tiny sizes: each file carries its own
        for part in ("config", "traffic"):
            _merge(cell[part], cell[part].get("rehearsal", {}))
    for part, change in (overrides or {}).items():
        _merge(cell[part], change)

    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = \
                f"{flags} --xla_force_host_platform_device_count={chips}"
        os.environ.setdefault("PADDLE_TPU_AUTOBENCH_CACHE", "0")
        cache = gate = "(none: rehearsal)"
    else:
        cache, gate = place_caches()

    import jax
    device = device_info(jax)
    if not rehearsal and device["platform"] != "tpu":
        print(f"benchmark: needs a TPU and will not carry on elsewhere: "
              f"jax found {device}", file=sys.stderr)
        return 2
    if device["count"] < chips:
        print(f"benchmark: workload {args.workload} needs {chips} chips, "
              f"jax found {device['count']}", file=sys.stderr)
        return 2

    ctx = Context(cell, args, t_start, rehearsal, control)

    def on_event(name, **_kw):
        if name.startswith("/jax/compilation_cache/cache_"):
            kind = name.rsplit("_", 1)[1]
            if kind in ctx.cache_events:
                ctx.cache_events[kind] += 1
    jax.monitoring.register_event_listener(on_event)

    def on_duration(name, _secs, **_kw):
        # fires once for every program jax lowers, cached or not
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            ctx.lowerings += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    say(f"benchmark {args.workload} seed={ctx.seed} seconds={ctx.seconds} "
        f"trace={int(ctx.trace)} device={device}"
        + (" REHEARSAL (CPU, tiny sizes; no number below is a device "
           "metric)" if rehearsal else ""))
    say(f"compile cache: {cache}; gate cache: {gate}")

    runner = importlib.import_module(
        f"benchmark.runners.{ctx.config['runner']}")
    run = runner.run(ctx)       # {"end_to_end", "attempted", "failed", ...}

    say(f"gate decisions ({len(run['gate'])}): "
        + json.dumps(run["gate"], sort_keys=True))
    say(f"compile cache events: {ctx.cache_events['hits']} hits, "
        f"{ctx.cache_events['misses']} misses; collector pauses after "
        f"warm-up: {ctx.gc_pauses}")

    name = ctx.cell["name"]
    e2e = {m["name"]: m for m in ctx.bench["end_to_end"]
           if reports(m, name)}
    if ctx.trace:
        metrics = layer_metrics(ctx, run)
    else:
        metrics = {}
        for mname, m in e2e.items():
            v = run["end_to_end"].get(mname)
            if v is None or not math.isfinite(v):
                ctx.check(f"{mname} has a finite value", math.inf, 0)
                continue
            metrics[mname] = {"value": float(v), "unit": m["unit"]}

    dev = dict(device, memory_peak_bytes=run["memory_peak_bytes"])
    line = {"correct": ctx.correct, "attempted": int(run["attempted"]),
            "failed": int(run["failed"]), "metrics": metrics, "device": dev,
            "checks": ctx.checks}
    if ctx.trace and run.get("trace") is not None:
        tr = run["trace"]
        dev["busy_s"] = tr.busy_s
        dev["window_s"] = tr.window_s
        line["breakdown"] = tr.breakdown()
    if ctx.control:
        line["control"] = ctx.control_readings
    if rehearsal:
        # counts only: a CPU run is never a device metric
        line = {"rehearsal": True, "correct": ctx.correct,
                "attempted": line["attempted"], "failed": line["failed"],
                "would_report": sorted(metrics), "device": device,
                "checks": [[c[0], None, c[2], c[3]] for c in ctx.checks],
                **({"control": ctx.control_readings, "program": {
                    c[0]: c[1] for c in ctx.checks}} if ctx.control else {})}
    print(json.dumps(line), flush=True)
    return 0

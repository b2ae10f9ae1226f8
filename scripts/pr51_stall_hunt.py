#!/usr/bin/env python3
"""The stall hunt of ISSUE 51: untraced runs of one cell exactly as
`benchmark/run.py` makes them, a process a run, and after each what the
tracer's ring says of its window: the steps that took longer than their
like (`benchmark/readers/pauses.py::account`), by phase, with the
collector's passes and jax's own work inside them, and what jax's work
took before the window. A builder's tool, never part of the benchmark.

    python3 scripts/pr51_stall_hunt.py --workload <cell> --runs N
        [--seed0 S] [--seconds 40] [--until-ms 1000] [--inject-ms 500]
        [--trace 1] [--rehearse] [--quick] [--env PADDLE_TPU_TRACE=0]

Every run gets a seed of its own (`seed0 + k`) and prints one line
`HUNT {...}`; the parent ends with `HUNT-SUMMARY {...}` and stops early
once a run holds a stall of `--until-ms` or more. `--inject-ms` arms the
injector `Engine.step` already calls (`fault_injection.injector()`, point
`serving_decode`) for ONE step in the middle of the window and disarms
it: the readers must then find that step, all of it before `ready`.
Each run's account is also written to chiprun_out/pr51/.
"""
import time

_T0 = time.perf_counter()

import argparse
import collections
import importlib
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "pr51")


def _args(argv):
    ap = argparse.ArgumentParser(prog="scripts/pr51_stall_hunt.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=1)
    ap.add_argument("--seed0", type=int, default=2147483751)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--until-ms", type=float, default=None)
    ap.add_argument("--inject-ms", type=float, default=0.0)
    ap.add_argument("--over-ms", type=float, default=50.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="the reference replays one request: for rates "
                         "and stalls, not for limits")
    ap.add_argument("--env", action="append", default=[])
    ap.add_argument("--tag", default="")
    ap.add_argument("--one", type=int, default=None,
                    help="(the child) run this seed in this process")
    return ap.parse_args(argv)


# ---------------------------------------------------------------------------
# the child: one run
# ---------------------------------------------------------------------------

def _arm_one_stall(ms: float, seconds: float, ramp_s: float):
    """`Engine.step` sleeps `ms` in `engine.wait`, before it reads, in the
    first step that starts `ramp + seconds / 2` after the harness froze
    the collector (the window's middle), and in no other."""
    from benchmark.lib import harness
    from paddle_tpu.distributed.fleet.runtime import fault_injection
    from paddle_tpu.serving import Engine
    state = {"at": None, "done": False}
    inner_freeze, inner_step = harness.Context.freeze_gc, Engine.step

    def freeze_gc(self):
        inner_freeze(self)
        state["at"] = time.perf_counter() + ramp_s + seconds / 2

    def step(self):
        arm = not state["done"] and state["at"] is not None \
            and time.perf_counter() >= state["at"]
        if not arm:
            return inner_step(self)
        inj = fault_injection.injector()
        inj.stall, inj.stall_point = 1e-3 * ms, "serving_decode"
        try:
            return inner_step(self)
        finally:
            inj.stall, state["done"] = 0.0, True
    harness.Context.freeze_gc, Engine.step = freeze_gc, step
    return state


def _counters():
    from paddle_tpu.observability import registry
    out = {}
    for name in ("paddle_tpu_host_gc_seconds_total",
                 "paddle_tpu_jit_seconds_total"):
        m = registry.REGISTRY.get(name)
        if m is not None:
            out[name] = {",".join(k): c.value for k, c in m._series()}
    for name in ("paddle_tpu_trace_dropped_total",
                 "paddle_tpu_trace_ring_high_water"):
        m = registry.REGISTRY.get(name)
        out[name] = None if m is None else m.value
    return out


def _by_function(pause_spans, end, top=10):
    """The functions with the most `jit.trace` + `jit.lower` time before
    `end`, by `fun_name` (a lowering's `jit(f)` is `f`'s); nested traces
    count in both."""
    by = collections.defaultdict(lambda: [0.0, 0.0, 0, 0])
    for s in pause_spans:
        if s["name"] in ("jit.trace", "jit.lower") and s["end"] <= end:
            fn = str(s["attrs"].get("fun_name", "?"))
            if fn.startswith("jit(") and fn.endswith(")"):
                fn = fn[4:-1]
            lower = s["name"] == "jit.lower"
            by[fn][lower] += s["end"] - s["start"]
            by[fn][2 + lower] += 1
    rows = sorted(by.items(), key=lambda kv: -(kv[1][0] + kv[1][1]))[:top]
    return [{"fun_name": fn, "trace_s": round(t, 3), "lower_s": round(lo, 3),
             "traces": nt, "lowers": nl} for fn, (t, lo, nt, nl) in rows]


def one(a) -> int:
    from benchmark.lib import harness
    cell = harness.load_cell(a.workload)
    runner = importlib.import_module(
        f"benchmark.runners.{cell['config']['runner']}")
    inner, kept = runner.run, {}

    def run(ctx):
        kept["run"] = inner(ctx)
        return kept["run"]
    runner.run = run
    injected = None
    if a.inject_ms:
        tr = dict(cell["traffic"])
        if a.rehearse:
            tr.update(tr.get("rehearsal", {}))
        injected = _arm_one_stall(a.inject_ms, a.seconds,
                                  float(tr.get("ramp_s", 0.0)))
    rc = harness.main(["--workload", a.workload, "--seed", str(a.one),
                       "--seconds", str(a.seconds), "--trace",
                       str(a.trace)], t_start=_T0, rehearsal=a.rehearse,
                      overrides={"config": {"correct": {
                          "sample_requests": 1}}} if a.quick else None)
    if "run" not in kept:
        return rc or 1
    from benchmark.readers import pauses
    run = kept["run"]
    acc = pauses.account(run, a.over_ms)
    spans = pauses.pause_spans(run) or []
    end = pauses._setup_end(run)
    kept_s = collections.defaultdict(float)
    for s in spans:
        kept_s[s["name"]] += s["end"] - s["start"]
    e2e = run["end_to_end"]
    line = {
        "workload": a.workload, "seed": a.one, "tag": a.tag,
        "trace_env": os.environ.get("PADDLE_TPU_TRACE", "1"),
        "end_to_end": {k: (None if v is None else float(v))
                       for k, v in e2e.items()},
        "stall_time_share": pauses.stall_time_share(run, a.over_ms),
        "stall_longest_ms": pauses.stall_longest_ms(run, a.over_ms),
        "stall_wait_ms": pauses.stall_wait_ms(run, a.over_ms),
        "stall_host_ms": pauses.stall_host_ms(run, a.over_ms),
        "host_pause_ms": pauses.host_pause_ms(run),
        "setup_trace_lower_s": pauses.setup_trace_lower_s(run),
        "setup_compile_load_s": pauses.setup_compile_load_s(run),
        "account": acc,
        "injected": None if injected is None else injected["done"],
        "top_trace_lower": _by_function(spans, end) if end else [],
        # what the floor left out: the counters hold every pause, the
        # ring those of a millisecond or longer (nested traces twice)
        "counters": _counters(),
        "kept_span_seconds": dict(kept_s),
        "pause_spans": len(spans),
    }
    os.makedirs(OUT, exist_ok=True)
    name = f"{a.workload}.{a.one}{'.' + a.tag if a.tag else ''}.json"
    with open(os.path.join(OUT, name), "w") as f:
        json.dump(line, f, indent=1, default=str)
    print("HUNT " + json.dumps(line, default=str), flush=True)
    return rc


# ---------------------------------------------------------------------------
# the parent: a process a run
# ---------------------------------------------------------------------------

def many(a, argv) -> int:
    env = dict(os.environ)
    for pair in a.env:
        k, v = pair.split("=", 1)
        env[k] = v
    lines, rc = [], 0
    for k in range(a.runs):
        seed = a.seed0 + k
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), *argv,
             "--one", str(seed)], env=env, cwd=ROOT, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        got = [ln for ln in p.stdout.splitlines() if ln.startswith("HUNT ")]
        result = [ln for ln in p.stdout.splitlines()
                  if ln.startswith('{"correct"') or ln.startswith(
                      '{"rehearsal"')]
        if p.returncode or not got:
            rc = p.returncode or 1
            os.makedirs(OUT, exist_ok=True)
            log = os.path.join(OUT, f"{a.workload}.{seed}.{a.tag}.failed")
            with open(log, "w") as f:
                f.write(p.stdout)
            print(f"run {k} seed {seed} FAILED rc={p.returncode}: {log}\n"
                  + p.stdout[-6000:], flush=True)
            continue
        line = json.loads(got[-1][5:])
        line["correct"] = json.loads(result[-1]).get("correct") \
            if result else None
        lines.append(line)
        st = (line["account"] or {}).get("stalled", [])
        print(f"run {k} seed {seed} correct={line['correct']} "
              f"e2e={line['end_to_end']} stalls={len(st)} "
              f"share={line['stall_time_share']} "
              f"longest_ms={line['stall_longest_ms']} "
              f"wait_ms={line['stall_wait_ms']} "
              f"host_ms={line['stall_host_ms']} "
              f"pause_ms={line['host_pause_ms']} "
              f"unpredicted={(line['account'] or {}).get('unpredicted')} "
              f"setup trace+lower={line['setup_trace_lower_s']} "
              f"compile+load={line['setup_compile_load_s']} "
              f"high_water="
              f"{line['counters']['paddle_tpu_trace_ring_high_water']} "
              f"dropped="
              f"{line['counters']['paddle_tpu_trace_dropped_total']}",
              flush=True)
        for s in st[:8]:
            print("   stall " + json.dumps(s), flush=True)
        if a.until_ms and (line["stall_longest_ms"] or 0) >= a.until_ms:
            print(f"caught a stall of {a.until_ms} ms or more in run {k}",
                  flush=True)
            break
    summary = {
        "workload": a.workload, "runs": len(lines), "tag": a.tag,
        "env": a.env,
        "runs_with_a_stall": sum(
            1 for ln in lines if (ln["account"] or {}).get("stalled")),
        "by_run": [{"seed": ln["seed"], "correct": ln["correct"],
                    **ln["end_to_end"],
                    "stall_time_share": ln["stall_time_share"],
                    "stall_longest_ms": ln["stall_longest_ms"],
                    "stall_wait_ms": ln["stall_wait_ms"],
                    "stall_host_ms": ln["stall_host_ms"],
                    "host_pause_ms": ln["host_pause_ms"],
                    "setup_trace_lower_s": ln["setup_trace_lower_s"],
                    "setup_compile_load_s": ln["setup_compile_load_s"]}
                   for ln in lines]}
    print("HUNT-SUMMARY " + json.dumps(summary), flush=True)
    return rc


def main(argv) -> int:
    a = _args(argv)
    return one(a) if a.one is not None else many(a, argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

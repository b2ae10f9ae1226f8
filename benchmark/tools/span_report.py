#!/usr/bin/env python3
"""One run of a cell exactly as `run.py` makes it, then everything the
program's own spans say about it, traced or not: a builder's tool for the
numbers of PERF.md that a result line does not carry (an untraced run
prints no per-layer metric). Never part of the benchmark's own runs.

    python3 benchmark/tools/span_report.py --workload <name> --seed <n>
        --seconds <s> [--trace 0|1] [--rehearse]
    python3 benchmark/tools/span_report.py --cost    # what a span costs here

After the result line it prints one line `SPANS {...}`: the host's
milliseconds by phase, the share of `engine.step` its phases cover, the
queue wait beside the harness's TTFT and its generator's lateness, the
prefill's share of TTFT, the idle time by phase, the longest distance
between two steps (the profiler's start), the stalled steps and the ring's
drops.
"""
import time

_T0 = time.perf_counter()

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import harness, stats  # noqa: E402
from benchmark.readers import counters, spans as reader  # noqa: E402

PHASES = ("engine.admit", "engine.build", "engine.decode", "engine.emit")


def cost(n=20000):
    """Microseconds a `span()` and a `record()` cost on this host, with
    the tracer at its default, without its profiler bridge, and off."""
    from paddle_tpu.observability.tracing import Tracer
    out = {}
    for label, kw in (("default", {}), ("no_bridge", {"bridge_jax": False}),
                      ("off", {"enabled": False})):
        t = Tracer(max_spans=2 * n, **kw)
        with t.span("warm"):
            pass
        best_s = best_r = float("inf")
        for _ in range(3):
            t.clear()
            t0 = time.perf_counter()
            for i in range(n):
                with t.span("x", step=i):
                    pass
            best_s = min(best_s, (time.perf_counter() - t0) / n * 1e6)
            t.clear()
            t0 = time.perf_counter()
            for i in range(n):
                t.record("q", 1.0, 2.0, trace_id="abc", request=i,
                         outcome="admitted")
            best_r = min(best_r, (time.perf_counter() - t0) / n * 1e6)
        out[label] = {"span_us": best_s, "record_us": best_r}
    return out


def report(run) -> dict:
    spans = reader.program_spans(run)
    out = {"dropped": reader._dropped(),
           "spans_in_ring": None if spans is None else len(spans)}
    if not spans:
        return out
    if run.get("kind") == "train":
        n = len(run["step_seconds"])
        by = {}
        for s in spans:
            if s["name"].startswith("train."):
                by.setdefault(s["name"], []).append(
                    1e3 * (s["end"] - s["start"]))
        out["train_ms"] = {k: {"p50": stats.median(v[-n:]),
                               "max": max(v[-n:]), "n": len(v[-n:])}
                           for k, v in by.items()}
        return out
    got = reader._steps(run)
    if got is None:
        return out
    steps, kids, _caused = got
    out["steps"] = len(steps)
    cover = [sum(p["end"] - p["start"] for p in ph)
             / (st["end"] - st["start"]) for st, ph in steps]
    out["cover_min"], out["cover_p50"] = min(cover), stats.median(cover)
    out["host_ms_p50"] = {
        n: reader.phase_self_ms_p50(run, n)
        for n in PHASES + ("engine.dispatch", "engine.wait")}
    d = [1e3 * (st["end"] - st["start"]) for st, _ph in steps]
    out["step_ms"] = {"p50": stats.median(d), "max": max(d)}
    out["stall_steps"] = reader.stall_steps(run, 3)
    # where a stalled step spent its time: waiting for the device
    # (engine.wait) or on the host
    limit = 3 * stats.median(d)
    out["stalled"] = [
        {"step": st["attrs"].get("step"), "ms": 1e3 * (st["end"] - st["start"]),
         "admitted": st["attrs"].get("admitted"),
         "phases_ms": {p["name"]: 1e3 * (p["end"] - p["start"])
                       for ph in phases
                       for p in [ph] + kids.get(ph["span_id"], [])}}
        for st, phases in steps if 1e3 * (st["end"] - st["start"]) > limit][:5]
    # how near the slots' end the window ran
    out["slots_full_share"] = sum(
        1 for st, _ph in steps if st["attrs"].get("active") == run["slots"]
    ) / len(steps)
    out["queue_depth_max"] = max(st["attrs"].get("queue_depth", 0)
                                 for st, _ph in steps)
    # the same phases by the older clock (StepSampler: one step in 50,
    # behind a fence; the last sample, milliseconds)
    from paddle_tpu.observability import perf
    out["step_sampler_last_ms"] = {
        k: {"samples": v["samples"],
            **{ph: 1e3 * x for ph, x in v["phases"].items()}}
        for k, v in perf.breakdowns().items()}
    out["pool_live_of_reserved"] = reader.pool_live_of_reserved(run)
    # from one step's end to the next one's start: the caller's loop, and
    # once in a traced run the profiler's start
    ends = sorted((st["start"], st["end"]) for st, _ph in steps)
    between = [1e3 * (b[0] - a[1]) for a, b in zip(ends, ends[1:])]
    out["between_steps_ms"] = {"p50": stats.median(between),
                               "max": max(between)}
    t0, t1 = run["window"]
    started = (run.get("trace_span") or (None,))[0]
    if started is not None:
        gap = [b[0] - a[1] for a, b in zip(ends, ends[1:])
               if a[1] <= started + 0.5 and b[0] >= started - 2.0]
        out["profiler_start_gap_ms"] = 1e3 * max(gap) if gap else None
    # TTFT in its parts, over the arrivals of the window before the profiler
    hi = min(t1, started) if started is not None else t1
    loop = run["loop"]
    if run["traffic"]["loop"] == "open":
        late = [1e3 * d for due, d in loop.late if t0 <= due < hi]
        ttft = [1e3 * s for due, s in loop.ttft if t0 <= due < hi]
        q = [s for s in spans if s["name"] == "scheduler.queue"
             and s["attrs"].get("outcome") == "admitted"
             and t0 <= s["start"] < hi]
        pre = {s["attrs"].get("request"): s for s in spans
               if s["name"] == "engine.prefill"}
        behind, prefill = [], []
        for s in q:
            p = pre.get(s["attrs"].get("request"))
            if p is not None:
                behind.append(1e3 * (p["start"] - s["end"]))
                prefill.append(1e3 * (p["end"] - p["start"]))
        pct = lambda v: {"p50": stats.percentile(v, 50),
                         "p90": stats.percentile(v, 90), "n": len(v)}
        out["ttft_parts_ms_before_profiler"] = {
            "generator_late": pct(late),
            "queue_wait": pct([1e3 * (s["end"] - s["start"]) for s in q]),
            "behind_other_prefills": pct(behind), "prefill": pct(prefill),
            "ttft_harness": pct(ttft)}
        out["blocked_max"] = max((s["attrs"].get("blocked", 0) for s in q),
                                 default=0)
        out["ttft_p90_ms_whole_window"] = counters.ttft_percentile_ms(run, 90)
        out["queue_wait_p90_ms_whole_window"] = stats.percentile(
            [1e3 * (s["end"] - s["start"]) for s in spans
             if s["name"] == "scheduler.queue" and t0 <= s["start"] < t1], 90)
    idle = reader.idle_by_phase(run)
    if idle is not None:
        parts, window = idle
        out["idle_share"] = {k: 100.0 * v / window for k, v in parts.items()}
        out["idle_share"]["sum"] = 100.0 * sum(parts.values()) / window
        out["idle_share"]["device_idle_share"] = \
            100.0 * run["trace"].idle_share()
        out["trace_offset_ns"] = reader.trace_offset_ns(run)
    return out


def main(argv):
    if "--cost" in argv:
        print("SPAN_COST " + json.dumps(cost()), flush=True)
        return 0
    import importlib
    cell = harness.load_cell(argv[argv.index("--workload") + 1])
    runner = importlib.import_module(
        f"benchmark.runners.{cell['config']['runner']}")
    kept, inner = {}, runner.run

    def run(ctx):
        out = inner(ctx)
        kept.update(out)
        return out
    runner.run = run
    rehearsal = "--rehearse" in argv    # CPU, tiny sizes: finds faults
    rc = harness.main([a for a in argv if a != "--rehearse"], t_start=_T0,
                      rehearsal=rehearsal)
    if kept:
        print("SPANS " + json.dumps(report(kept)), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

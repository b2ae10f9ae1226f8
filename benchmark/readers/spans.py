"""Readers of the spans the program records about itself
(`paddle_tpu.observability.tracing.TRACER`: the phases of `Engine.step`,
`scheduler.queue`, `train.step`), read in process once the run is over.

Clocks. A span's stamps are `TRACER.clock()`, the harness's are
`time.perf_counter()`, the profiler's are nanoseconds of its own. The
first offset is taken by reading the two clocks back to back; the second
is the median distance between the `bench.step` events of the reduced
trace and the `t0` the harness stamped before the same steps. With both,
every program span lies on the device trace's timeline, and the idle time
of chip 0 is given to the phase of `engine.step` that covers it.

A program that records no such span (the parent of the PR that added
them) gives every reader nothing to read: each returns None. So does a
trace ring that dropped a span in this process, which is one run.
"""
from __future__ import annotations

import json
import os
import time

from ..lib import stats, trace as trace_lib

STEP = "engine.step"
BENCH_STEP = "bench.step"       # runners/serve.py::SPAN
MATCH_NS = 200_000              # a step and its span agree on their length


def _dropped() -> float:
    from paddle_tpu.observability import registry
    m = registry.REGISTRY.get("paddle_tpu_trace_dropped_total")
    return float(m.value) if m is not None else 0.0


def clock_offset(tracer) -> float:
    """Seconds to add to a stamp of the tracer's clock to get the
    harness's (`time.perf_counter`), from reading them back to back."""
    clock = getattr(tracer, "clock", time.monotonic)
    a = clock()
    b = time.perf_counter()
    c = clock()
    return b - 0.5 * (a + c)


def program_spans(run):
    """The ring's finished spans as dicts with `start` and `end` on the
    harness's clock, oldest first; None if the ring dropped any."""
    if "_program_spans" not in run:
        from paddle_tpu.observability import tracing
        spans = None
        if not _dropped():
            off = clock_offset(tracing.TRACER)
            spans = [{"name": s.name, "start": s.start + off,
                      "end": s.end + off, "span_id": s.span_id,
                      "parent_id": s.parent_id,
                      "caused_by": getattr(s, "caused_by", None),
                      "trace_id": s.trace_id, "attrs": dict(s.attrs)}
                     for s in tracing.TRACER.spans() if s.end is not None]
            spans.sort(key=lambda s: s["start"])
        run["_program_spans"] = spans
        _keep(run, spans)
    return run["_program_spans"]


def trace_offset_ns(run):
    """Nanoseconds to add to a harness stamp (in ns) to get the
    profiler's, from the `bench.step` events and the same steps' `t0`;
    None without a trace or a step in it. A call of `step()` that did no
    work has an event and no entry in `loop.steps`: the two lists are
    paired in order, by length."""
    tr, loop = run.get("trace"), run.get("loop")
    if tr is None or loop is None or run.get("trace_span") is None:
        return None
    a, b = run["trace_span"]
    if a is None:
        return None
    events = [(s, e) for n, s, e in tr.spans if n == BENCH_STEP]
    diffs, j = [], 0
    for t0, t1, *_rest in loop.steps:
        if t0 < a or t1 > b:
            continue
        while j < len(events) and abs(
                (events[j][1] - events[j][0]) - (t1 - t0) * 1e9) > MATCH_NS:
            j += 1
        if j == len(events):
            break
        diffs.append(events[j][0] - t0 * 1e9)
        j += 1
    return stats.median(diffs)


def _keep(run, spans):
    """With BENCH_KEEP_TRACE set, the mapped spans beside `trace.json`."""
    keep = os.environ.get("BENCH_KEEP_TRACE")
    if not keep or spans is None:
        return
    off = trace_offset_ns(run)
    os.makedirs(keep, exist_ok=True)
    with open(os.path.join(keep, "program_spans.json"), "w") as f:
        json.dump({"harness_to_trace_ns": off, "window": run.get("window"),
                   "trace_span": run.get("trace_span"), "spans": spans}, f,
                  default=str)


# ---------------------------------------------------------------------------
# steps and their phases
# ---------------------------------------------------------------------------

def _steps(run, lo=None, hi=None, idle_too=False):
    """[(step, its phases in order)] of the `engine.step` spans that
    ended in (lo, hi], by default in the window; without `idle_too`, of
    those that decoded. Also the spans each span is the parent of, and
    those it caused."""
    if run.get("kind") != "serve":
        return None
    if lo is None:
        lo, hi = run["window"]
    spans = program_spans(run)
    if not spans:
        return None
    if "_span_links" not in run:
        kids, caused = {}, {}
        for s in spans:             # in order of their starts
            if s["parent_id"] is not None:
                kids.setdefault(s["parent_id"], []).append(s)
            if s["caused_by"] is not None:
                caused.setdefault(s["caused_by"], []).append(s)
        run["_span_links"] = kids, caused
    kids, caused = run["_span_links"]
    steps = [(s, kids.get(s["span_id"], [])) for s in spans
             if s["name"] == STEP and lo < s["end"] <= hi
             and (idle_too or not s["attrs"].get("idle"))]
    return (steps, kids, caused) if steps else None


def phase_self_ms_p50(run, span):
    """Median over the window's decoding steps of the time `span` took
    less the spans inside it (`engine.admit` causes its prefills: what is
    left is expiry and admission). `span` is a phase of the step, or a
    child of one (`engine.dispatch`)."""
    got = _steps(run)
    if got is None:
        return None
    steps, kids, caused = got
    own = []
    for _st, phases in steps:
        for ph in phases:
            for s in [ph] + kids.get(ph["span_id"], []):
                if s["name"] == span:
                    inner = kids.get(s["span_id"], []) \
                        + caused.get(s["span_id"], [])
                    own.append(1e3 * (s["end"] - s["start"] - sum(
                        c["end"] - c["start"] for c in inner)))
    return stats.median(own)


def stall_steps(run, times):
    """Decoding steps of the window longer than `times` their median."""
    got = _steps(run)
    if got is None:
        return None
    d = [st["end"] - st["start"] for st, _ph in got[0]]
    limit = times * stats.median(d)
    return float(sum(1 for x in d if x > limit))


def pool_live_of_reserved(run):
    """Median over the window's decoding steps of the pages that hold a
    token over the pages the pool has handed out."""
    got = _steps(run)
    if got is None:
        return None
    shares = [100.0 * st["attrs"]["pages_live"] / st["attrs"]["pages_reserved"]
              for st, _ph in got[0] if st["attrs"].get("pages_reserved")]
    return stats.median(shares)


def queue_wait_ms(run, p):
    """Percentile of the time between `submit` and a slot, over the
    requests that were queued in the window before the profiler started
    (its start is a pause of the host's own, and the traced run is the
    one that reads this)."""
    if run.get("kind") != "serve":
        return None
    spans = program_spans(run)
    if not spans:
        return None
    lo, hi = run["window"]
    started = (run.get("trace_span") or (None,))[0]
    if started is not None:
        hi = min(hi, started)
    waits = [1e3 * (s["end"] - s["start"]) for s in spans
             if s["name"] == "scheduler.queue"
             and s["attrs"].get("outcome") == "admitted"
             and lo <= s["start"] < hi]
    return stats.percentile(waits, p)


def train_dispatch_ms_p50(run):
    """Median `train.step` (the host's part of a step: the batch's
    transfer and the jitted call) over the steps the step time is read
    from."""
    n = len(run.get("step_seconds") or ())
    spans = program_spans(run) if n else None
    if not spans:
        return None
    d = [1e3 * (s["end"] - s["start"]) for s in spans
         if s["name"] == "train.step"]
    return stats.median(d[-n:])


# ---------------------------------------------------------------------------
# the device's idle time, by the phase that covers it
# ---------------------------------------------------------------------------

def idle_by_phase(run):
    """{phase name or "outside": nanoseconds of chip 0's idle time under
    it} over the traced window, and the window's length; None without a
    trace, a device in it or a step span. Inside a step a moment belongs
    to the phase that started last before it (the first phase from the
    step's start), so the parts add up to the idle time exactly."""
    if "_idle_by_phase" not in run:
        run["_idle_by_phase"] = _idle_by_phase(run)
    return run["_idle_by_phase"]


def _idle_by_phase(run):
    tr = run.get("trace")
    off = trace_offset_ns(run)
    if off is None or not tr.busy or tr.t1 <= tr.t0:
        return None
    to_ns = lambda t: t * 1e9 + off
    a, b = run["trace_span"]
    got = _steps(run, a, b + 1.0, idle_too=True)
    if got is None:
        return None
    steps = got[0]
    busy = tr.busy[min(tr.busy)]
    edges = [tr.t0] + [x for s, e in busy for x in (s, e)] + [tr.t1]
    idle = [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    owned = {}
    for st, phases in steps:
        cuts = [to_ns(st["start"])] + [to_ns(p["start"])
                                       for p in phases[1:]] \
            + [to_ns(st["end"])]
        for ph, lo, hi in zip(phases, cuts, cuts[1:]):
            owned.setdefault(ph["name"], []).append([lo, hi])
    out, inside = {}, 0
    for name, cover in owned.items():
        out[name] = trace_lib.covered(idle, trace_lib.union(cover))
        inside += out[name]
    out["outside"] = trace_lib.total(idle) - inside
    return out, tr.t1 - tr.t0


def idle_share_under(run, span):
    """Share of the traced window in which chip 0 ran nothing while the
    host was in phase `span` of a step ("outside": in no step at all, the
    caller's loop). The shares of the four phases and "outside" add up to
    `device_idle_share`."""
    got = idle_by_phase(run)
    if got is None:
        return None
    parts, window = got
    return 100.0 * parts.get(span, 0) / window

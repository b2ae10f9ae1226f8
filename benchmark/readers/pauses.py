"""Readers of what interrupts the program from inside its own process
(ISSUE 51): the tracer's pause spans (`host.gc` for a pass of the
collector; `jit.trace`, `jit.lower`, `jit.compile`, `jit.cache_load` for
jax's own work on any jitted function), and the window's steps read
against their like.

A step's length is not a stall by itself: since ISSUE 44 `engine.wait`
holds the step's prefills, and a prompt of 16,384 tokens is 0.6 s. So a
step is held against the steps of its KIND, the sorted tuple of the
`bucket`s of the prefills its `engine.admit` caused (`()` for a bare
decode): its EXCESS is its length less the median of its kind, and it is
STALLED where the excess passes `over_ms`. A kind with fewer than
`MIN_KIND` steps is predicted as the bare median plus, for each bucket,
what the OTHER steps that carry that bucket alone add; where there is no
such step the kind cannot be predicted, and its steps are left out and
counted (`unpredicted`). A step whose decode or prefill says `compiled`
is set-up, and the harness checks that the window has none.

The excess is split the same way: `engine.wait` from its start to its
stamp `ready` is the host waiting for the device or the runtime
(`stall_wait_ms`), the rest of the step is the host's own (`stall_host_ms`:
admit, build up to its stamp `filled`, the batch's transfers after it,
dispatch, read-back, emit and what lies between them).

All readers read the ring over the whole window, traced or not (the
device trace holds its last 5 s), and return None where the ring dropped a
span or holds no pause span at all: the program of before ISSUE 51.
"""
from __future__ import annotations

from ..lib import stats, trace as trace_lib
from .admission import _offset
from .spans import _steps, program_spans

GC = "host.gc"
TRACE_LOWER = ("jit.trace", "jit.lower")
COMPILE_LOAD = ("jit.compile", "jit.cache_load")
PAUSES = (GC,) + TRACE_LOWER + COMPILE_LOAD
MIN_KIND = 5        # steps of one kind whose median is taken as it is
# the parts a step's length is split into, in order; `between` is what
# lies in no phase
PARTS = ("admit", "build", "transfer", "dispatch", "wait", "readback",
         "emit", "between")


def pause_spans(run):
    """The ring's pause spans, oldest first; None if the ring dropped a
    span or holds none (the program records none)."""
    if "_pause_spans" not in run:
        spans = program_spans(run)
        got = [s for s in spans or () if s["name"] in PAUSES]
        run["_pause_spans"] = got or None
    return run["_pause_spans"]


def _seconds(spans, names, before=None):
    """Wall seconds covered by the spans of `names` (nested or on
    several threads, a moment counts once) that end before `before`."""
    return trace_lib.union(
        [s["start"], s["end"]] for s in spans if s["name"] in names
        and (before is None or s["end"] <= before))


# ---------------------------------------------------------------------------
# set-up: what jax's own work took before the window
# ---------------------------------------------------------------------------

def _setup_end(run):
    """Where set-up ends on the harness's clock: the window's start; for
    a trainer the start of its last `train.step` (the window lowers and
    compiles nothing: the harness checks it)."""
    if run.get("window"):
        return run["window"][0]
    last = [s["start"] for s in program_spans(run) or ()
            if s["name"] == "train.step"]
    return last[-1] if last else None


def _setup_parts(run):
    """(seconds tracing and lowering, seconds compiling and loading)
    before the window. The kernel gate compiles its candidates INSIDE a
    trace: such a moment is the compile's, so the two never count a
    moment twice and add up to no more than `setup_s`."""
    pauses = pause_spans(run)
    end = _setup_end(run) if pauses else None
    if end is None:
        return None
    compile_load = _seconds(pauses, COMPILE_LOAD, end)
    trace_lower = _seconds(pauses, TRACE_LOWER, end)
    both = trace_lib.covered(trace_lower, compile_load)
    return trace_lib.total(trace_lower) - both, trace_lib.total(compile_load)


def setup_trace_lower_s(run):
    """Seconds of set-up in `jit.trace` and `jit.lower`: paid by a warm
    run too, whatever the compile cache holds."""
    got = _setup_parts(run)
    return None if got is None else got[0]


def setup_compile_load_s(run):
    """Seconds of set-up in `jit.compile` and `jit.cache_load`: what the
    compile cache saves, and what loading from it costs."""
    got = _setup_parts(run)
    return None if got is None else got[1]


# ---------------------------------------------------------------------------
# the window's steps against their like
# ---------------------------------------------------------------------------

def _parts(st, phases, kids, off):
    """One step's length in seconds by part (PARTS). `off` puts the
    stamps `filled` and `ready`, attributes on the tracer's clock, on the
    clock of the spans' own ends (`admission._offset`)."""
    out = dict.fromkeys(PARTS, 0.0)
    inside = 0.0
    for ph in phases:
        d = ph["end"] - ph["start"]
        inside += d
        name = ph["name"].split(".", 1)[1]
        if name == "build" and "filled" in ph["attrs"]:
            # the numpy batch up to the stamp, its transfers after it
            transfer = ph["end"] - (ph["attrs"]["filled"] + off)
            out["build"] += d - transfer
            out["transfer"] += transfer
            continue
        if name != "decode":    # a phase this file has no name for
            out[name if name in out else "between"] += d
            continue
        covered = 0.0
        for c in kids.get(ph["span_id"], []):
            cd = c["end"] - c["start"]
            covered += cd
            if c["name"] == "engine.dispatch":
                out["dispatch"] += cd
            elif c["name"] == "engine.wait":
                # with nothing to read there is no stamp, and no wait
                ready = c["attrs"].get("ready")
                back = cd if ready is None else c["end"] - (ready + off)
                out["wait"] += cd - back
                out["readback"] += back
        out["between"] += d - covered
    out["between"] += (st["end"] - st["start"]) - inside
    return out


def account(run, over_ms=50.0):
    """The stall account of the window: a dict with `window_s`, `steps`
    (decoding steps read), `unpredicted` (steps of a kind too rare to
    predict), `medians` ({kind: its median length in ms}) and `stalled`,
    one dict a stalled step, longest excess first: `step`, `at_s` (its
    start after the window's), `ms`, `kind`, `excess_ms`, `wait_ms` and
    `host_ms` (which add up to it), `by_part_ms` (each part's length less
    its kind's median) and `pauses` (the pause spans inside it: name, ms,
    `fun_name`, the name of the span they interrupted). None where there
    is nothing to read."""
    key = f"_stall_account[{float(over_ms)}]"
    if key not in run:
        run[key] = _account(run, float(over_ms))
    return run[key]


def _account(run, over_ms):
    pauses = pause_spans(run)
    got = _steps(run) if pauses else None
    if got is None:
        return None
    steps, kids, caused = got
    off = _offset(run)
    if off is None:
        return None
    by_id = {s["span_id"]: s for s in program_spans(run)}
    rows = []
    for st, phases in steps:
        prefills = [p for ph in phases if ph["name"] == "engine.admit"
                    for p in caused.get(ph["span_id"], [])
                    if p["name"] == "engine.prefill"]
        decode = [ph for ph in phases if ph["name"] == "engine.decode"]
        if any(s["attrs"].get("compiled") for s in prefills + decode):
            continue
        rows.append({
            "st": st,
            "kind": tuple(sorted(int(p["attrs"]["bucket"])
                                 for p in prefills)),
            # seconds: the step's length and each of its parts
            "s": {"len": st["end"] - st["start"],
                  **_parts(st, phases, kids, off)}})
    if not rows:
        return None
    by_kind = {}
    for r in rows:
        by_kind.setdefault(r["kind"], []).append(r)
    fields = ("len",) + PARTS

    def medians(group):
        return {f: stats.median([r["s"][f] for r in group])
                for f in fields}
    known = {k: medians(g) for k, g in by_kind.items()
             if len(g) >= MIN_KIND}
    bare = known.get(())

    def predict(r):
        if r["kind"] in known:
            return known[r["kind"]]
        if bare is None:
            return None
        want = dict(bare)
        for b in r["kind"]:
            alone = [o for o in by_kind.get((b,), ()) if o is not r]
            if not alone:
                return None
            add = medians(alone)
            for f in fields:
                want[f] += add[f] - bare[f]
        return want

    lo, hi = run["window"]
    stalled, unpredicted = [], 0
    for r in rows:
        want = predict(r)
        if want is None:
            unpredicted += 1
            continue
        excess = r["s"]["len"] - want["len"]
        if 1e3 * excess <= over_ms:
            continue
        st = r["st"]
        wait = min(max(r["s"]["wait"] - want["wait"], 0.0), excess)
        inside = [p for p in pauses
                  if p["end"] > st["start"] and p["start"] < st["end"]]
        stalled.append({
            "step": st["attrs"].get("step"), "at_s": st["start"] - lo,
            "ms": 1e3 * r["s"]["len"], "kind": list(r["kind"]),
            "excess_ms": 1e3 * excess, "wait_ms": 1e3 * wait,
            "host_ms": 1e3 * (excess - wait),
            "by_part_ms": {f: 1e3 * (r["s"][f] - want[f])
                           for f in PARTS},
            "pauses": [{
                "name": p["name"],
                "ms": 1e3 * (min(p["end"], st["end"])
                             - max(p["start"], st["start"])),
                **({"fun_name": p["attrs"]["fun_name"]}
                   if "fun_name" in p["attrs"] else {}),
                "during": (by_id.get(p["attrs"].get("during")) or {})
                .get("name")} for p in inside]})
    stalled.sort(key=lambda s: -s["excess_ms"])
    return {"window_s": hi - lo, "steps": len(rows),
            "unpredicted": unpredicted, "over_ms": over_ms,
            "medians": {str(list(k)): 1e3 * m["len"]
                        for k, m in sorted(known.items())},
            "stalled": stalled}


def _of_stalled(run, over_ms, field):
    got = account(run, over_ms)
    if got is None:
        return None
    return sum(s[field] for s in got["stalled"])


def stall_time_share(run, over_ms=50.0):
    """What stalls took from the rate: the stalled steps' excess over the
    window's length, in percent."""
    got = account(run, over_ms)
    if got is None:
        return None
    return 100.0 * 1e-3 * _of_stalled(run, over_ms, "excess_ms") \
        / got["window_s"]


def stall_longest_ms(run, over_ms=50.0):
    """The largest excess of the window; 0 where no step is stalled."""
    got = account(run, over_ms)
    if got is None:
        return None
    return max((s["excess_ms"] for s in got["stalled"]), default=0.0)


def stall_wait_ms(run, over_ms=50.0):
    """Of the stalled excess, the part inside `engine.wait` before
    `ready`: the host was waiting for the device or the runtime."""
    return _of_stalled(run, over_ms, "wait_ms")


def stall_host_ms(run, over_ms=50.0):
    """The rest of the stalled excess: the host itself stood still."""
    return _of_stalled(run, over_ms, "host_ms")


def host_pause_ms(run):
    """The collector's passes and jax's own work inside the window's
    decoding steps, in wall milliseconds: the part of a host stall the
    process can explain."""
    pauses = pause_spans(run)
    got = _steps(run) if pauses else None
    if got is None:
        return None
    inside = trace_lib.union([st["start"], st["end"]] for st, _ph in got[0])
    return 1e3 * trace_lib.covered(_seconds(pauses, PAUSES), inside)

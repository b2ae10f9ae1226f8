"""The readers of the program's own spans (readers/spans.py): synthetic
spans laid over the two steps recorded on the chip (tests/data/), whose
answers are plain, and the rehearsal's list of what a traced run would
report."""
import collections
import json
import os
import subprocess
import sys
import time
import types

import pytest

from benchmark.lib import trace as trace_lib
from benchmark.readers import spans as reader
from paddle_tpu.observability.tracing import TRACER

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PROGRAM_BEHIND = 123.456    # harness clock minus the program's, seconds
H0 = 5000.0                 # the window's start on the harness's clock
PHASES = ("engine.admit", "engine.build", "engine.decode", "engine.emit")


def _step(t0, t1, no, admit, build, emit, prefill=0.0, live=40, reserved=64):
    """One `engine.step` from t0 to t1 (harness clock) as the program
    records it: phases one after the other, `engine.decode` taking what
    the others leave; returns the step's Span."""
    at = lambda t: t - PROGRAM_BEHIND
    st = TRACER.record("engine.step", at(t0), at(t1), step=no, active=32,
                       admitted=int(bool(prefill)), queue_depth=0,
                       pages_reserved=reserved, pages_live=live)
    cuts = [t0, t0 + admit, t0 + admit + build, t1 - emit, t1]
    kids = [TRACER.record(name, at(a), at(b), trace_id=st.trace_id,
                          parent_id=st.span_id)
            for name, a, b in zip(PHASES, cuts, cuts[1:])]
    if prefill:
        p = TRACER.record("engine.prefill", at(t0 + 0.0002),
                          at(t0 + 0.0002 + prefill), trace_id="a-request")
        p.caused_by = kids[0].span_id
    dec = kids[2]
    TRACER.record("engine.dispatch", dec.start, dec.start + 0.0007,
                  trace_id=st.trace_id, parent_id=dec.span_id)
    TRACER.record("engine.wait", dec.start + 0.0007, dec.end,
                  trace_id=st.trace_id, parent_id=dec.span_id)
    return st


@pytest.fixture
def run(monkeypatch):
    """Ten steps of 95 ms and one of 400 ms, then the two recorded steps
    under the profiler; between the two a call of `step()` that did no
    work (an event, and no entry in `loop.steps`)."""
    # the counter is the process's: an earlier test may have raised it
    monkeypatch.setattr(reader, "_dropped", lambda: 0.0)
    monkeypatch.setattr(TRACER, "clock",
                        lambda: time.perf_counter() - PROGRAM_BEHIND)
    monkeypatch.setattr(TRACER, "enabled", True)
    TRACER.clear()
    with open(os.path.join(HERE, "data", "serve_two_steps.json")) as f:
        doc = json.load(f)
    host = next(p for p in doc["trace"]["planes"]
                if p["name"] == trace_lib.HOST_PLANE)
    events = host["lines"][0]["events"]
    (_n, s1, d1), (_n, s2, d2) = events
    events.insert(1, ["bench.step", s1 + d1 + 60_000, 20_000])
    steps, t = [], H0
    for k in range(11):
        dur = 0.4 if k == 10 else 0.095
        _step(t + 1e-5, t + dur - 5e-6, k, 0.0003, 0.0011, 0.0009)
        steps.append((t, t + dur, 0, 32, 9000, 0))
        t += dur
    traced_from = t + 0.001
    to_trace_ns = s1 - traced_from * 1e9        # harness ns -> profiler ns
    harness = lambda ns: (ns - to_trace_ns) / 1e9
    for k, (s, d) in enumerate(((s1, d1), (s2, d2))):
        # the harness stamps before it opens its span and after it closes
        steps.append((harness(s) - 3e-6, harness(s + d) + 2e-6,
                      1 - k, 32, 9000, 256 * (1 - k)))
        _step(harness(s) + 1e-5, harness(s + d) - 5e-6, 11 + k,
              0.013 if k == 0 else 0.0003, 0.0011, 0.0009,
              prefill=0.0125 if k == 0 else 0.0, live=48 - 16 * k)
    return {"kind": "serve", "trace": trace_lib.Reduced(doc["trace"]),
            "loop": types.SimpleNamespace(steps=steps),
            "window": (H0, steps[-1][1]),
            "trace_span": (steps[-2][0] - 1e-4, steps[-1][1] + 1e-4),
            "to_trace_ns": to_trace_ns}


def test_both_clock_offsets_are_recovered(run):
    assert reader.clock_offset(TRACER) == pytest.approx(PROGRAM_BEHIND,
                                                        abs=1e-4)
    assert reader.trace_offset_ns(run) == pytest.approx(run["to_trace_ns"],
                                                        abs=1e5)
    # and a span of the program lands where its step is in the trace
    st = [s for s in reader.program_spans(run) if s["name"] == "engine.step"]
    first_traced = st[-2]["start"] * 1e9 + reader.trace_offset_ns(run)
    assert first_traced == pytest.approx(run["trace"].spans[0][1] + 1e4,
                                         abs=1e5)


def test_idle_shares_add_up_to_the_idle_share(run):
    names = list(PHASES) + ["outside"]
    shares = {n: reader.idle_share_under(run, n) for n in names}
    assert all(v is not None and v >= 0 for v in shares.values())
    assert sum(shares.values()) == pytest.approx(
        100.0 * run["trace"].idle_share(), abs=1e-6)
    # what lies in no step, counted another way: the idle time of the
    # window less the idle time inside the two engine.step spans
    tr = run["trace"]
    busy = tr.busy[0]
    off = reader.trace_offset_ns(run)
    inside = [[s["start"] * 1e9 + off, s["end"] * 1e9 + off]
              for s in reader.program_spans(run)[-30:]
              if s["name"] == "engine.step"][-2:]
    idle_inside = sum(e - s for s, e in inside) \
        - trace_lib.covered(inside, busy)
    idle_all = (tr.t1 - tr.t0) - trace_lib.total(busy)
    assert shares["outside"] == pytest.approx(
        100.0 * (idle_all - idle_inside) / (tr.t1 - tr.t0), abs=1e-6)
    # and one phase the same way: emit is the last 0.9 ms of each step
    emit = [[e - 0.9e6, e] for _s, e in inside]
    assert shares["engine.emit"] == pytest.approx(
        100.0 * (1.8e6 - trace_lib.covered(emit, busy)) / (tr.t1 - tr.t0),
        abs=1e-6)


def test_host_phases_stalls_and_pages(run):
    # medians over thirteen steps: eleven admit 0.3 ms, one with a prefill
    # 13 ms of which 12.5 are the prefill's
    assert reader.phase_self_ms_p50(run, "engine.admit") == \
        pytest.approx(0.3, abs=1e-6)
    assert reader.phase_self_ms_p50(run, "engine.build") == \
        pytest.approx(1.1, abs=1e-6)
    assert reader.phase_self_ms_p50(run, "engine.dispatch") == \
        pytest.approx(0.7, abs=1e-6)
    assert reader.phase_self_ms_p50(run, "engine.emit") == \
        pytest.approx(0.9, abs=1e-6)
    run2 = dict(run, window=(run["loop"].steps[-2][0] - 1e-3,
                             run["window"][1]))
    for k in ("_program_spans", "_span_links"):
        run2.pop(k, None)
    # the two traced steps alone: 13 - 12.5 and 0.3
    assert reader.phase_self_ms_p50(run2, "engine.admit") == \
        pytest.approx(0.4, abs=1e-6)
    assert reader.stall_steps(run, 3) == 1.0        # the 400 ms step
    assert reader.stall_steps(run2, 3) == 0.0
    assert reader.pool_live_of_reserved(run) == pytest.approx(100 * 40 / 64)
    assert reader.pool_live_of_reserved(run2) == pytest.approx(100 * 40 / 64)


def test_queue_wait_reads_the_requests_queued_before_the_profiler(run):
    at = lambda t: t - PROGRAM_BEHIND
    for i, wait in enumerate((0.010, 0.020, 0.030, 0.040, 0.090)):
        TRACER.record("scheduler.queue", at(H0 + 0.1 * i),
                      at(H0 + 0.1 * i + wait), trace_id=f"r{i}", request=i,
                      outcome="admitted", slot=i, prompt_len=8, blocked=0)
    # not read: one that never got a slot, one queued before the window,
    # one queued once the profiler had started
    TRACER.record("scheduler.queue", at(H0 + 0.7), at(H0 + 0.9),
                  trace_id="x", request=9, outcome="expired", slot=None,
                  prompt_len=8, blocked=4)
    TRACER.record("scheduler.queue", at(H0 - 1.0), at(H0 + 0.5),
                  trace_id="y", request=10, outcome="admitted", slot=1,
                  prompt_len=8, blocked=0)
    TRACER.record("scheduler.queue", at(run["trace_span"][0] + 0.01),
                  at(run["trace_span"][0] + 0.8), trace_id="z", request=11,
                  outcome="admitted", slot=2, prompt_len=8, blocked=0)
    assert reader.queue_wait_ms(run, 90) == pytest.approx(90.0, abs=1e-6)
    assert reader.queue_wait_ms(run, 50) == pytest.approx(30.0, abs=1e-6)


def test_train_dispatch_reads_the_last_steps(run):
    TRACER.clear()
    for i, d in enumerate((0.5, 0.004, 0.002, 0.003)):
        TRACER.record("train.step", 10.0 + i, 10.0 + i + d, step=i + 1)
    train = {"kind": "train", "step_seconds": [0.48, 0.48, 0.48]}
    assert reader.train_dispatch_ms_p50(train) == pytest.approx(3.0)
    assert reader.train_dispatch_ms_p50({"kind": "serve"}) is None


def test_nothing_to_read_gives_none(run):
    """A program without these spans (the parent), a run without a trace,
    a trainer asked for a serving metric."""
    no_trace = dict(run, trace=None, trace_span=(None, None))
    no_trace.pop("_idle_by_phase", None)
    assert reader.idle_share_under(no_trace, "engine.admit") is None
    assert reader.phase_self_ms_p50(no_trace, "engine.build") is not None
    TRACER.clear()
    TRACER.record("engine.decode", 1.0, 2.0)        # what the parent has
    bare = {k: v for k, v in run.items() if not k.startswith("_")}
    for fn, args in ((reader.phase_self_ms_p50, ("engine.build",)),
                     (reader.idle_share_under, ("outside",)),
                     (reader.stall_steps, (3,)),
                     (reader.pool_live_of_reserved, ()),
                     (reader.queue_wait_ms, (90,))):
        assert fn(dict(bare), *args) is None
        assert fn({"kind": "train", "step_seconds": [1.0]}, *args) is None
    assert reader.train_dispatch_ms_p50(
        {"kind": "train", "step_seconds": [1.0]}) is None


def test_a_risen_drop_counter_gives_none(run, monkeypatch):
    monkeypatch.undo()              # the real counter, the real clock
    before = reader._dropped()
    monkeypatch.setattr(TRACER, "_spans", collections.deque(maxlen=4))
    for i in range(6):
        TRACER.record("engine.step", float(i), i + 0.5)
    assert reader._dropped() == before + 2
    bare = {k: v for k, v in run.items() if not k.startswith("_")}
    assert reader.program_spans(dict(bare)) is None
    assert reader.stall_steps(dict(bare), 3) is None
    assert reader.idle_share_under(dict(bare), "outside") is None


def test_keep_trace_writes_the_mapped_spans(run, monkeypatch, tmp_path):
    monkeypatch.setenv("BENCH_KEEP_TRACE", str(tmp_path))
    n = len(reader.program_spans(run))
    with open(tmp_path / "program_spans.json") as f:
        doc = json.load(f)
    assert len(doc["spans"]) == n
    assert doc["harness_to_trace_ns"] == pytest.approx(run["to_trace_ns"],
                                                       abs=1e5)


@pytest.mark.parametrize("workload,wants", [
    ("gpt_1p3b_serve.mixed_open",
     ["queue_wait_p90_ms", "host_admit_ms_p50", "host_build_ms_p50",
      "host_dispatch_ms_p50", "host_emit_ms_p50", "stall_steps",
      "pool_live_of_reserved"]),
    ("gpt_350m_train.b16s1024", ["train_dispatch_ms_p50"])])
def test_rehearsal_lists_the_span_metrics(workload, wants):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearse.py"),
         "--workload", workload, "--trace", "1"], capture_output=True,
        text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(wants) <= set(line["would_report"])


def test_every_span_metric_has_its_file_and_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["source"] == "program_span"]
    assert len(mine) == 13
    reports = {m["name"]: [w for w in m.get("workloads", ())]
               for m in bench["end_to_end"]}
    for m in mine:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"].startswith("spans:")
        assert hasattr(reader, spec["reader"].split(":")[1])
        assert (spec["unit"], spec["layer"], spec["moves"]) == \
            (m["unit"], m["layer"], m["moves"])
        # each cell of the metric reports the end-to-end metric it moves
        assert set(m["workloads"]) <= set(reports[m["moves"]])
        if m["unit"] == "%":
            assert spec["max"] == 100

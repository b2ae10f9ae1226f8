"""The spans the program records about itself (ISSUE 24): the phases of
`Engine.step`, the queue span of `Scheduler`, the trainer's dispatch
spans, and what `Tracer` grew for them (`record`, `clock`, `caused_by`,
counter span ids). The names and attributes below are a contract: the
benchmark's readers (benchmark/readers/spans.py) and PERF.md read them."""
import threading
import time

import numpy as np
import pytest

from paddle_tpu.models.gpt import GPTConfig
from paddle_tpu.observability import flight, registry, tracing
from paddle_tpu.observability.tracing import TRACER, Tracer
from paddle_tpu.serving import (Engine, GPTDecodeModel, PagePool, Request,
                                Scheduler)

PHASES = ["engine.admit", "engine.build", "engine.decode", "engine.emit"]


@pytest.fixture(scope="module")
def engine():
    model = GPTDecodeModel(GPTConfig.tiny(num_layers=1), seed=0)
    eng = Engine(model, num_slots=4, num_pages=32, page_size=8,
                 max_seq_len=64)
    # compile the buckets the tests use, so that no test times a compile
    for n in (5, 12):
        eng.submit(np.arange(1, n + 1), max_new_tokens=2)
    eng.run_until_idle()
    return eng


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 100, size=n)


def _run(eng, *requests):
    """Submit, run until idle; returns (requests, the spans recorded)."""
    TRACER.clear()
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in requests]
    eng.run_until_idle()
    return reqs, TRACER.spans()


def _children(spans, parent):
    return sorted((s for s in spans if s.parent_id == parent.span_id),
                  key=lambda s: s.start)


# -- Engine.step ------------------------------------------------------------

def test_phases_nest_under_the_step_in_order_and_do_not_overlap(engine):
    _reqs, spans = _run(engine, (_prompt(5), 6), (_prompt(12, 1), 4))
    steps = [s for s in spans if s.name == "engine.step"]
    busy = [s for s in steps if not s.attrs.get("idle")]
    assert len(busy) >= 5
    for st in busy:
        kids = _children(spans, st)
        assert [k.name for k in kids] == PHASES
        # every one inside the step, one after the other
        for k in kids:
            assert st.start <= k.start <= k.end <= st.end
        for a, b in zip(kids, kids[1:]):
            assert a.end <= b.start
        dec = _children(spans, kids[2])
        assert [k.name for k in dec] == ["engine.dispatch", "engine.wait"]
        assert kids[3].attrs["finished"] >= 0
    # so over the run the phases hold some of the steps' wall time and
    # never more than it. How much falls between two phases is the
    # machine's load, not the program: the benchmark reads it on the chip
    covered = sum(k.duration() for st in busy for k in _children(spans, st))
    assert 0 < covered <= sum(st.duration() for st in busy)
    nos = [st.attrs["step"] for st in steps]
    assert nos == list(range(nos[0], nos[0] + len(nos)))


def test_step_attributes_say_what_the_step_held(engine):
    _reqs, spans = _run(engine, (_prompt(5), 6), (_prompt(12, 1), 4))
    steps = [s for s in spans if s.name == "engine.step"]
    first = steps[0]
    assert first.attrs["queue_depth"] == 2 and first.attrs["admitted"] == 2
    assert first.attrs["active"] == 2
    # reserved: the worst case of both, ceil(11/8) + ceil(16/8) pages;
    # live: the pages that hold a token, ceil(6/8) + ceil(13/8)
    assert first.attrs["pages_reserved"] == 2 + 2
    assert first.attrs["pages_live"] == 1 + 2
    assert _children(spans, first)[0].attrs["admitted"] == 2
    later = steps[2]
    assert later.attrs["queue_depth"] == 0 and later.attrs["admitted"] == 0
    assert later.attrs["pages_live"] <= later.attrs["pages_reserved"]


def test_a_step_with_no_active_slot_is_idle_with_admit_alone(engine):
    # the request's deadline has passed by the first step: it never runs
    TRACER.clear()
    req = engine.submit(_prompt(5), max_new_tokens=3, deadline=-1.0)
    engine.run_until_idle()
    spans = TRACER.spans()
    assert req.status == "deadline"
    steps = [s for s in spans if s.name == "engine.step"]
    assert len(steps) == 1 and steps[0].attrs["idle"] is True
    assert [k.name for k in _children(spans, steps[0])] == ["engine.admit"]
    # and an engine with nothing queued or running records nothing
    TRACER.clear()
    assert engine.step() is False
    assert TRACER.spans() == []


def test_a_request_of_one_token_is_read_in_the_step_that_admitted_it(engine):
    # the one token of this request is the prefill's: nothing to decode,
    # but the token is read behind the dispatch like any other (ISSUE 44)
    reqs, spans = _run(engine, (_prompt(5), 1))
    steps = [s for s in spans if s.name == "engine.step"]
    assert len(steps) == 1 and "idle" not in steps[0].attrs
    kids = _children(spans, steps[0])
    assert [k.name for k in kids] == PHASES
    dispatch, wait = _children(spans, kids[2])
    assert kids[2].attrs["active"] == 0 and kids[2].attrs["ahead"] is False
    assert wait.attrs["of_step"] is None and wait.attrs["first_tokens"] == 1
    assert kids[3].attrs["finished"] == 1 and reqs[0].status == "done"


def test_prefill_names_the_admit_that_ran_it(engine):
    reqs, spans = _run(engine, (_prompt(5), 3), (_prompt(12, 1), 3))
    admit = next(s for s in spans if s.name == "engine.admit")
    step = next(s for s in spans if s.name == "engine.step")
    assert admit.parent_id == step.span_id
    prefills = [s for s in spans if s.name == "engine.prefill"]
    assert len(prefills) == 2
    for p, r in zip(prefills, reqs):
        # the request's own trace, and still linked to the step
        assert p.trace_id == r.trace_id != step.trace_id
        assert p.parent_id is None and p.caused_by == admit.span_id
        assert admit.start <= p.start and p.end <= admit.end
        assert p.to_event()["args"]["caused_by"] == admit.span_id


def test_at_most_ten_spans_a_decode_step(engine):
    _reqs, spans = _run(engine, (_prompt(5), 8))
    steps = [s for s in spans if s.name == "engine.step"]
    # the prefill's token and the first decode's dispatch, then a token
    # read and a decode dispatched a step, then the last token's read
    assert len(steps) == 8
    # a step that admits nothing: itself, four phases, dispatch and wait
    assert len(spans) - 2 == 7 * len(steps)     # + one prefill, one queue
    # the first step also holds the prefill, and ends the queue span
    inside = [sum(1 for s in spans if st.start <= s.start and s.end <= st.end)
              for st in steps]
    assert inside == [8] + [7] * 7 and inside[0] + 1 <= 10


# -- the stamps inside the spans (ISSUE 34) ------------------------------------

def _stamped(spans):
    """(span, stamp) of every span that carries one of the two stamps."""
    return [(s, s.attrs[key]) for s in spans
            for name, key in (("engine.build", "filled"),
                              ("engine.wait", "ready"))
            if s.name == name and key in s.attrs]


def test_the_stamps_lie_inside_their_spans(engine):
    _reqs, spans = _run(engine, (_prompt(5), 6), (_prompt(12, 1), 4))
    by_name = {}
    for s, stamp in _stamped(spans):
        assert s.start <= stamp <= s.end
        by_name.setdefault(s.name, []).append(s)
    # every build, and every wait that had tokens to read: a decode's, or
    # (the first step's) the admitted requests' first
    assert by_name["engine.build"] == [s for s in spans
                                       if s.name == "engine.build"]
    assert by_name["engine.wait"] == [
        s for s in spans
        if s.name == "engine.wait" and (s.attrs["of_step"] is not None
                                        or s.attrs["first_tokens"])]
    assert len(by_name["engine.wait"]) >= 6


def test_a_wait_says_what_it_read_and_is_ready_only_if_it_read(engine):
    _reqs, spans = _run(engine, (_prompt(5), 4))
    waits = [s for s in spans if s.name == "engine.wait"]
    # the first step dispatches decode 1 and has none before it to read,
    # but its own prefill's first token (ISSUE 44)
    assert waits[0].attrs["of_step"] is None
    assert [w.attrs["first_tokens"] for w in waits] == [1, 0, 0, 0]
    assert all("ready" in w.attrs for w in waits) and len(waits) > 1
    # a whole prompt in the prefix cache: no program, nothing unread
    model = GPTDecodeModel(GPTConfig.tiny(num_layers=1), seed=0)
    eng = Engine(model, num_slots=2, num_pages=16, page_size=8,
                 max_seq_len=32, prefix_cache_pages=8)
    _run(eng, (np.arange(1, 17), 2))
    _reqs, spans = _run(eng, (np.arange(1, 17), 2))
    first = next(s for s in spans if s.name == "engine.wait")
    assert first.attrs["of_step"] is None and not first.attrs["first_tokens"]
    assert "ready" not in first.attrs
    assert not [s for s in spans if s.name == "engine.prefill"]


def test_the_stamps_are_the_tracers_clock(engine, monkeypatch):
    ticks = iter(range(10**6))
    monkeypatch.setattr(TRACER, "clock", lambda: next(ticks))
    _reqs, spans = _run(engine, (_prompt(5), 4), (_prompt(12, 1), 3))
    got = _stamped(spans)
    assert {s.name for s, _ in got} == {"engine.build", "engine.wait"}
    for s, stamp in got:
        # a reading of the counter between the span's own two
        assert isinstance(stamp, int) and s.start < stamp < s.end


def test_a_prefill_span_says_what_was_asked_and_what_ran(engine):
    """What `prefill_padding_share` reads: the positions a prefill was
    asked for and the bucket its program ran, on the span alone (no
    counter beside it, and no fence or stamp of the prefill's own: the
    span is the dispatch, its token is read in `engine.wait`)."""
    _reqs, spans = _run(engine, (_prompt(5), 3), (_prompt(12, 1), 3),
                        (_prompt(9, 2), 2))
    prefills = [s for s in spans if s.name == "engine.prefill"]
    asked = sum(p.attrs["prompt_len"] - p.attrs["cached_tokens"]
                for p in prefills)
    ran = sum(p.attrs["bucket"] for p in prefills)
    assert (asked, ran) == (5 + 12 + 9, 8 + 16 + 16)
    assert all("ready" not in p.attrs for p in prefills)


@pytest.mark.parametrize("recording", [True, False])
def test_the_sampled_breakdown_is_read_off_the_spans(engine, monkeypatch,
                                                     recording):
    """One clock in `Engine.step`: the perf plane's sampled phases are
    the lengths of that step's spans, with the ring recording or not (a
    span stamps its start and end either way: PADDLE_TPU_TRACE=0)."""
    from paddle_tpu.observability import perf
    name = f"engine:{engine.engine_id}"
    monkeypatch.setattr(TRACER, "enabled", recording)
    every = perf.sampling_every()
    perf.set_every(1)
    try:
        seen = (perf.breakdowns().get(name) or {"samples": 0})["samples"]
        _reqs, spans = _run(engine, (_prompt(5), 5))
    finally:
        perf.set_every(every)
    bd = perf.breakdowns()[name]
    # every step that read a decode's tokens was sampled: of the five
    # tokens the prefill made one, read in the first step's wait
    assert bd["samples"] - seen == 4
    assert set(bd["phases"]) == {"host", "dispatch", "device", "transfer"}
    assert all(v >= 0.0 for v in bd["phases"].values())
    if not recording:
        assert spans == []
        return
    # the last sample is the last step's
    last = [s for s in spans if s.name == "engine.step"][-1]
    kids = {k.name: k for k in _children(spans, last)}
    dec = {k.name: k for k in _children(spans, kids["engine.decode"])}
    wait = dec["engine.wait"]
    ph = bd["phases"]
    assert ph["device"] + ph["transfer"] == pytest.approx(wait.duration(),
                                                          abs=1e-9)
    assert ph["device"] == pytest.approx(wait.attrs["ready"] - wait.start,
                                         abs=1e-9)
    assert ph["host"] == pytest.approx(kids["engine.build"].duration(),
                                       abs=1e-9)
    assert ph["dispatch"] == pytest.approx(
        dec["engine.dispatch"].duration(), abs=1e-9)


def test_compiled_marks_the_call_that_compiled():
    model = GPTDecodeModel(GPTConfig.tiny(num_layers=1), seed=0)
    eng = Engine(model, num_slots=2, num_pages=16, page_size=8,
                 max_seq_len=32)
    _reqs, spans = _run(eng, (_prompt(5), 3))
    _reqs, again = _run(eng, (_prompt(5, 1), 3))
    for name in ("engine.prefill", "engine.decode"):
        first = [s for s in spans if s.name == name]
        assert first[0].attrs.get("compiled") is True
        assert not any(s.attrs.get("compiled") for s in first[1:])
        assert not any(s.attrs.get("compiled") for s in again
                       if s.name == name)


def test_the_step_flight_event_is_gone_and_the_request_events_stay(engine):
    flight.RECORDER.clear()
    _run(engine, (_prompt(5), 4))
    kinds = [e.kind for e in flight.RECORDER.events("serving")]
    assert "step" not in kinds
    for kind in ("submit", "admit", "prefill", "evict"):
        assert kinds.count(kind) == 1


# -- Scheduler --------------------------------------------------------------

def test_queue_span_lasts_from_submit_to_admit(engine):
    TRACER.clear()
    reqs = [engine.submit(_prompt(5, i), max_new_tokens=3) for i in range(6)]
    time.sleep(0.02)                # they wait; four slots for six
    engine.run_until_idle()
    spans = [s for s in TRACER.spans() if s.name == "scheduler.queue"]
    assert len(spans) == 6
    by_req = {s.attrs["request"]: s for s in spans}
    for r in reqs:
        q = by_req[r.id]
        assert q.trace_id == r.trace_id
        assert q.attrs["outcome"] == "admitted"
        assert q.attrs["prompt_len"] == 5 and q.attrs["blocked"] == 0
        assert q.attrs["slot"] in range(4)
        # the scheduler's clock is the real one here: the same wait
        assert q.duration() == pytest.approx(
            r.started_at - r._queued_at, abs=1e-3)
        assert q.duration() >= 0.02
    # the last two waited for a slot through whole decode steps
    waits = sorted(s.duration() for s in spans)
    assert waits[-1] > waits[0]


def _sched(**kw):
    pool = PagePool(num_pages=4, page_size=4)
    return pool, Scheduler(pool, num_slots=2, max_seq_len=32, **kw)


def _req(n=4, new=4, **kw):
    r = Request(np.arange(1, n + 1), new, **kw)
    r.trace_id = tracing.new_trace_id()
    return r


@pytest.mark.parametrize("outcome", ["admitted", "expired", "shed",
                                     "cancelled"])
def test_queue_span_carries_the_trace_id_with_every_outcome(outcome):
    clock = [100.0]                 # a fake scheduler clock: not the span's
    _pool, s = _sched(now=lambda: clock[0], max_queue=1)
    TRACER.clear()
    t0 = TRACER.clock()
    r = s.submit(_req(priority=2, deadline=101.0))
    if outcome == "admitted":
        assert s.admit() == [r]
    elif outcome == "expired":
        clock[0] = 102.0
        assert s.expire_deadlines() == [r]
    elif outcome == "shed":
        s.submit(_req(priority=0))  # a full queue sheds the lower tier
        assert r.status == "shed"
    else:
        assert s.cancel(r)
    q = [x for x in TRACER.spans() if x.name == "scheduler.queue"]
    assert len(q) == 1 and q[0].attrs["outcome"] == outcome
    assert q[0].trace_id == r.trace_id and q[0].attrs["request"] == r.id
    assert t0 <= q[0].start <= q[0].end <= TRACER.clock()
    assert q[0].end - q[0].start < 5.0      # not the fake clock's 100 s
    assert r._queue_t0 is None
    # finishing later records no second span
    if outcome == "admitted":
        s.evict(r, "done")
    assert len([x for x in TRACER.spans()
                if x.name == "scheduler.queue"]) == 1


def test_blocked_admissions_are_counted_on_the_request_and_the_registry():
    pool, s = _sched()
    TRACER.clear()
    a = s.submit(_req(8, 8))        # the whole pool
    b = s.submit(_req(4, 4))
    assert s.admit() == [a]         # and found no pages for b: one
    assert s.admit() == [] and s.admit() == []
    assert f'paddle_tpu_serving_admit_blocked_total{{inst="{s.inst}",' \
           f'reason="pool_full"}} 3' in registry.prometheus_text()
    s.evict(a, "done")
    assert s.admit() == [b]
    q = [x for x in TRACER.spans() if x.name == "scheduler.queue"]
    assert [x.attrs["blocked"] for x in q] == [0, 3]
    blocked = [e for e in flight.RECORDER.events("serving")
               if e.kind == "admit_blocked" and e.trace_id == b.trace_id]
    assert len(blocked) == 3        # beside each count, as before
    assert pool.used_pages == 2


# -- the trainer ------------------------------------------------------------

def test_train_step_has_put_and_dispatch_as_children():
    import jax
    from paddle_tpu.parallel.hybrid import HybridParallelTrainStep
    cfg = GPTConfig.tiny(num_layers=1)
    step = HybridParallelTrainStep(cfg, seed=0, devices=jax.devices()[:1])
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, size=(2, 16))
    TRACER.clear()
    for _ in range(3):
        loss = step(ids)
    jax.block_until_ready(loss)
    spans = TRACER.spans()
    steps = [s for s in spans if s.name == "train.step"]
    assert [s.attrs["step"] for s in steps] == [1, 2, 3]
    assert len(spans) == 9          # three a step
    for st in steps:
        assert [k.name for k in _children(spans, st)] == \
            ["train.put", "train.dispatch"]


def test_a_routed_models_step_carries_its_scopes_and_its_tally():
    """The step's program names its parts (`jax.named_scope`: what a
    profile groups by) and the trainer keeps the tally `tally_stats()`
    reads; the spans are the same three a step."""
    import jax
    from paddle_tpu.models import mellum
    from paddle_tpu.parallel.hybrid import HybridParallelTrainStep
    cfg = mellum.MellumConfig.tiny(experts_held=(0, 1, 2))
    step = HybridParallelTrainStep(mellum.MellumTrainModel(cfg), seed=0,
                                   devices=jax.devices()[:1])
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, size=(2, 32))
    text = step._jit_step.lower(
        step.params, step.opt_state, step._pows, step._tally,
        jax.numpy.asarray(ids), np.float32(1e-4),
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    for scope in ("attn.band", "attn.full", "moe.route", "moe.experts",
                  "head.loss", "adamw"):
        assert scope in text, scope
    TRACER.clear()
    for _ in range(2):
        loss = step(ids)
    jax.block_until_ready(loss)
    spans = TRACER.spans()
    assert [s.name for s in spans if s.name.startswith("train.")].count(
        "train.step") == 2
    for st in (s for s in spans if s.name == "train.step"):
        assert [k.name for k in _children(spans, st)] == \
            ["train.put", "train.dispatch"]
    t = step.tally_stats()
    assert t["pairs_routed"] == 2 * 4 * 64 * 2
    assert len(t["held_counts"]) == 4 and len(t["held_counts"][0]) == 3


# -- Tracer -----------------------------------------------------------------

def test_record_obeys_the_ring_the_sink_and_the_switch():
    t = Tracer(max_spans=4, enabled=True, bridge_jax=False)
    seen = []
    t.set_sink(seen.append)
    dropped = tracing._DROPPED.value
    for i in range(6):
        sp = t.record("q", 1.0 + i, 2.0 + i, trace_id="abc", n=i)
    assert sp.trace_id == "abc" and sp.parent_id is None
    assert (sp.start, sp.end, sp.attrs) == (6.0, 7.0, {"n": 5})
    assert [s.attrs["n"] for s in t.spans()] == [2, 3, 4, 5]
    assert tracing._DROPPED.value - dropped == 2
    assert len(seen) == 6
    # inside a span: the ambient trace unless told otherwise, and a parent
    # only when given one
    with t.span("outer") as outer:
        inner = t.record("q", 0.0, 1.0)
        given = t.record("q", 0.0, 1.0, parent_id=outer.span_id)
    assert inner.trace_id == outer.trace_id and inner.parent_id is None
    assert given.parent_id == outer.span_id
    t.enabled = False
    assert t.record("q", 0.0, 1.0) is None
    assert len(seen) == 9 and len(t.spans()) == 4


def test_default_ring_holds_a_benchmark_window_of_serving_spans():
    """A span reader reads nothing once the ring has dropped a span, so
    the process-wide ring holds a whole 40 s window of decode steps:
    seven spans a step, one a request, the warm-up's before them. At the
    12.4 ms steps of mixed_open since PR 29 that is 23,000 (16,384 spans
    dropped 3,578 of them); held to steps of 5 ms, with half as much
    room again."""
    assert tracing.TRACER._spans.maxlen == tracing.MAX_SPANS
    assert tracing.MAX_SPANS >= 1.5 * (7 * 40.0 / 5e-3 + 1000)


def test_switched_off_spans_propagate_ids_and_record_nothing():
    t = Tracer(enabled=False)
    with t.span("a", trace_id="feed") as a:
        assert t.current_trace_id() == "feed"
        with t.span("b") as b:
            assert b.trace_id == "feed" and b.parent_id == a.span_id
    assert t.current_span() is None and t.spans() == []


def test_every_stamp_is_the_tracers_clock():
    t = Tracer(bridge_jax=False)
    ticks = iter(range(10, 20))
    t.clock = lambda: next(ticks)
    with t.span("a") as a:
        with t.span("b") as b:
            pass
    assert (a.start, b.start, b.end, a.end) == (10, 11, 12, 13)
    assert Tracer().clock is time.monotonic


def test_rerooted_span_keeps_the_ambient_span_in_caused_by():
    t = Tracer(bridge_jax=False)
    with t.span("step") as step:
        with t.span("prefill", trace_id="req") as p:
            with t.span("inner") as inner:
                pass
        with t.span("same", trace_id=step.trace_id) as same:
            pass
    assert p.trace_id == "req" and p.parent_id is None
    assert p.caused_by == step.span_id
    assert inner.parent_id == p.span_id and inner.caused_by is None
    assert same.parent_id == step.span_id and same.caused_by is None
    assert "caused_by" not in step.to_event()["args"]


def test_span_ids_are_unique_across_threads():
    t = Tracer(max_spans=8192, bridge_jax=False)

    def work():
        for _ in range(500):
            with t.span("x"):
                t.record("y", 0.0, 1.0)
    threads = [threading.Thread(target=work) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    ids = [s.span_id for s in t.spans()]
    assert len(ids) == 8000 and len(set(ids)) == 8000
    assert all(len(i) == 16 for i in ids)


# -- a model with per-slot state and routed experts (ISSUE 26) ---------------

def test_prefill_names_its_slot_and_stats_name_the_experts_tallies():
    from paddle_tpu.models.lfm2 import LFM2Config
    from paddle_tpu.serving import HybridDecodeModel
    cfg = LFM2Config.tiny()
    eng = Engine(HybridDecodeModel(cfg, seed=0), num_slots=2, num_pages=16,
                 page_size=8, max_seq_len=32)
    reqs, spans = _run(eng, (_prompt(5), 3), (_prompt(9, 1), 3),
                       (_prompt(3, 2), 2))
    prefills = [s for s in spans if s.name == "engine.prefill"]
    assert len(prefills) == 3
    # the third request takes the slot the first to finish left
    assert [p.attrs["slot"] for p in prefills[:2]] == [0, 1]
    assert prefills[2].attrs["slot"] in (0, 1)
    for p in prefills:
        assert {"engine", "request", "prompt_len", "bucket",
                "cached_tokens", "slot"} <= set(p.attrs)
    st = eng.stats()
    for name in ("expert_tokens", "expert_touched",
                 "expert_load_max_over_mean", "experts_touched_share"):
        assert name in st
    assert np.asarray(st["expert_tokens"]).shape == (
        cfg.num_moe_layers, cfg.num_experts)
    assert registry.REGISTRY.get(
        "paddle_tpu_serving_slot_state_bytes") is not None
    # nothing is recorded per token: the same spans a step as before
    steps = [s for s in spans if s.name == "engine.step"
             and not s.attrs.get("idle")]
    for stp in steps:
        assert [k.name for k in _children(spans, stp)] == PHASES


def test_a_model_without_experts_reports_no_tally(engine):
    st = engine.stats()
    assert "expert_tokens" not in st and "experts_touched_share" not in st
    _reqs, spans = _run(engine, (_prompt(5), 2))
    assert next(s for s in spans
                if s.name == "engine.prefill").attrs["slot"] == 0


# -- a model whose layers run several times a token (ISSUE 30) ----------------

def test_a_looped_model_names_its_passes_and_its_tallies():
    from paddle_tpu.models.ouro import OuroConfig
    from paddle_tpu.serving import LoopedDecodeModel
    cfg = OuroConfig.tiny()
    eng = Engine(LoopedDecodeModel(cfg, seed=0), num_slots=2, num_pages=16,
                 page_size=8, max_seq_len=32)
    reqs, spans = _run(eng, (_prompt(5), 3), (_prompt(9, 1), 3))
    for name in ("engine.prefill", "engine.decode"):
        got = [s.attrs["passes"] for s in spans if s.name == name]
        assert got and set(got) == {cfg.total_ut_steps}
    st = eng.stats()
    fed = sum(int(r.prompt.size) + len(r.generated) - 1 for r in reqs)
    assert st["loop_passes"] == [fed] * 4
    assert st["loop_passes_per_token"] == 4.0
    assert len(st["exit_mass_share"]) == 4
    assert "expert_tokens" not in st
    # K and V, 12 rows of 4 heads of 16, float32: bytes one token holds
    gauge = registry.REGISTRY.get("paddle_tpu_serving_paged_bytes_per_token")
    assert gauge.labels(engine=eng.engine_id).value == 2 * 12 * 4 * 16 * 4
    # no span per pass: the loop is inside one program
    steps = [s for s in spans if s.name == "engine.step"
             and not s.attrs.get("idle")]
    for stp in steps:
        assert [k.name for k in _children(spans, stp)] == PHASES


def test_a_model_that_runs_its_layers_once_says_one_pass(engine):
    _reqs, spans = _run(engine, (_prompt(5), 2))
    assert {s.attrs["passes"] for s in spans
            if s.name in ("engine.prefill", "engine.decode")} == {1}
    st = engine.stats()
    assert "loop_passes" not in st and "exit_mass_share" not in st


def test_the_experts_stats_are_the_tallies_read_as_before():
    """What a tally means moved from the engine to the model
    (`DecodeModel.tally_stats`): the numbers are those the engine made."""
    from paddle_tpu.models.lfm2 import LFM2Config
    from paddle_tpu.serving import HybridDecodeModel
    cfg = LFM2Config.tiny()
    eng = Engine(HybridDecodeModel(cfg, seed=0), num_slots=2, num_pages=16,
                 page_size=8, max_seq_len=32)
    _run(eng, (_prompt(5), 3), (_prompt(9, 1), 4))
    pairs = np.asarray(eng.cache["expert_tokens"]).astype(np.int64)
    touched = np.asarray(eng.cache["expert_touched"]).astype(np.int64)
    st = eng.stats()
    assert st["expert_tokens"] == pairs.tolist()
    assert st["expert_touched"] == touched.tolist()
    mean = pairs.mean(axis=1)
    want = float((pairs.max(axis=1) / mean).mean()) \
        if (mean > 0).all() else None
    assert st["expert_load_max_over_mean"] == want
    assert st["experts_touched_share"] == pytest.approx(
        touched.sum() / (st["steps"] * touched.size))
    assert eng.stats()["experts_touched_share"] is None


def test_a_model_with_two_forms_of_attention_names_them_on_its_spans(engine):
    """`attn` on `engine.prefill` (expanded) and `engine.decode` (absorbed)
    for the latent model; absent for a model that has one form (GPT here,
    the hybrid and the looped model below), whose expert stats and passes
    are what they were."""
    from paddle_tpu.models.deepseek_v3 import DeepseekV3Config
    from paddle_tpu.models.lfm2 import LFM2Config
    from paddle_tpu.models.ouro import OuroConfig
    from paddle_tpu.serving import (HybridDecodeModel, LatentDecodeModel,
                                    LoopedDecodeModel)

    def attn_of(eng):
        _reqs, spans = _run(eng, (_prompt(5), 3), (_prompt(9, 1), 3))
        return {name: {s.attrs.get("attn") for s in spans if s.name == name}
                for name in ("engine.prefill", "engine.decode")}, spans

    kw = dict(num_slots=2, num_pages=16, page_size=8, max_seq_len=32)
    cfg = DeepseekV3Config.tiny()
    latent = Engine(LatentDecodeModel(cfg, seed=0), **kw)
    got, spans = attn_of(latent)
    assert got == {"engine.prefill": {"expanded"},
                   "engine.decode": {"absorbed"}}
    assert {s.attrs["passes"] for s in spans
            if s.name in ("engine.prefill", "engine.decode")} == {1}
    # the tallies under the hybrid model's names, through `tally_stats`
    st = latent.stats()
    pairs = np.asarray(latent.cache["expert_tokens"]).astype(np.int64)
    assert st["expert_tokens"] == pairs.tolist()
    assert pairs.shape == (cfg.num_moe_layers, cfg.n_routed_experts)
    assert pairs.sum() == (5 + 9 + 2 + 2) * cfg.num_moe_layers \
        * cfg.num_experts_per_tok
    assert "expert_load_max_over_mean" in st and "loop_passes" not in st
    # what the pool really holds a token: 3 rows padded to 128 lanes in
    # float32, and the routing part (2 expert layers x 2 experts, int8)
    gauge = registry.REGISTRY.get("paddle_tpu_serving_paged_bytes_per_token")
    assert gauge.labels(engine=latent.engine_id).value == 3 * 128 * 4 + 4
    # no span per layer or per form: the phases are the seven they were
    for stp in (s for s in spans if s.name == "engine.step"
                and not s.attrs.get("idle")):
        assert [k.name for k in _children(spans, stp)] == PHASES

    none = {"engine.prefill": {None}, "engine.decode": {None}}
    assert attn_of(engine)[0] == none
    hybrid = Engine(HybridDecodeModel(LFM2Config.tiny(), seed=0), **kw)
    assert attn_of(hybrid)[0] == none
    hst = hybrid.stats()
    hp = np.asarray(hybrid.cache["expert_tokens"]).astype(np.int64)
    assert hst["expert_tokens"] == hp.tolist()
    mean = hp.mean(axis=1)
    assert hst["expert_load_max_over_mean"] == (
        float((hp.max(axis=1) / mean).mean()) if (mean > 0).all() else None)
    assert attn_of(Engine(LoopedDecodeModel(OuroConfig.tiny(), seed=0),
                          **kw))[0] == none


# -- a model whose layers keep a recurrence's state a slot (ISSUE 42) ---------

@pytest.mark.parametrize("prompt,bucket,chunks", [(5, 8, 1), (17, 32, 1),
                                                  (300, 512, 2)])
def test_a_model_with_a_recurrence_names_its_scan_and_its_state_rows(
        engine, prompt, bucket, chunks):
    """`scan_len` / `scan_chunks` on `engine.prefill` (the bucket the
    chunked scan ran over, in chunks of 256 positions), `state_rows`
    on `engine.decode` (the live slots whose state the step advanced); all
    absent for a model without a recurrence; the state is a slot part and
    counts under the slot-state gauge."""
    from paddle_tpu.models.jamba import JambaConfig
    from paddle_tpu.serving import RecurrentDecodeModel
    cfg = JambaConfig.tiny()
    eng = Engine(RecurrentDecodeModel(cfg, seed=0), num_slots=2,
                 num_pages=80, page_size=8, max_seq_len=512)
    reqs, spans = _run(eng, (_prompt(prompt), 3), (_prompt(3, 1), 2))
    first = next(s for s in spans if s.name == "engine.prefill"
                 and s.attrs["request"] == reqs[0].id)
    assert (first.attrs["scan_len"], first.attrs["scan_chunks"]) \
        == (bucket, chunks)
    assert first.attrs["scan_len"] == first.attrs["bucket"]
    decodes = [s for s in spans if s.name == "engine.decode"]
    assert decodes and all(s.attrs["state_rows"] == s.attrs["active"]
                           for s in decodes)
    assert max(s.attrs["state_rows"] for s in decodes) == 2
    gauge = registry.REGISTRY.get("paddle_tpu_serving_slot_state_bytes")
    E, N, K = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    assert gauge.labels(engine=eng.engine_id).value \
        == 3 * 2 * (N * E * 4 + (K - 1) * E * 4)
    # no span per layer or per chunk: the phases are the seven they were
    for stp in (s for s in spans if s.name == "engine.step"
                and not s.attrs.get("idle")):
        assert [k.name for k in _children(spans, stp)] == PHASES
    _reqs, plain = _run(engine, (_prompt(5), 2))
    for s in plain:
        assert not {"scan_len", "scan_chunks", "state_rows"} & set(s.attrs)

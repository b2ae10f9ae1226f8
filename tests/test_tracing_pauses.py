"""The pause spans (ISSUE 51): what interrupts a thread from inside the
process, recorded by the tracer itself on `TRACER.clock()`. `host.gc` for
a pass of the collector, `jit.trace` / `jit.lower` / `jit.compile` /
`jit.cache_load` for jax's own work on any jitted function; each counted
in the registry whatever its length, kept as a span from a floor up, and
naming the span it interrupted in `during`, never in `parent_id` or
`caused_by`: the readers of `engine.step` take its children to be its
phases. tests/conftest.py takes the hooks off for the suite; each test
here installs them, with the floor it wants."""
import gc
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.observability import registry, tracing
from paddle_tpu.observability.tracing import TRACER, Tracer

from test_tracing_spans import PHASES, _children, engine  # noqa: F401

PAUSES = ("host.gc", "jit.trace", "jit.lower", "jit.compile",
          "jit.cache_load")


def _seconds(name, **labels):
    return registry.REGISTRY.get(name).labels(**labels).value


def _jit_seconds():
    return {st: _seconds("paddle_tpu_jit_seconds_total", stage=st)
            for st in ("trace", "lower", "compile")}


@pytest.fixture()
def tracer():
    """A tracer of the test's own with every pause kept (floor 0)."""
    tr = Tracer(enabled=True, bridge_jax=False)
    assert tr.install_pause_hooks(floor=0.0) is True
    yield tr
    tr.remove_pause_hooks()


@pytest.fixture()
def global_pauses():
    """The process's tracer with every pause kept, as the engine uses it."""
    assert TRACER.install_pause_hooks(floor=0.0) is True
    yield TRACER
    TRACER.remove_pause_hooks()


def _fresh_jit():
    """A jitted function no test has called: its first call traces,
    lowers and compiles."""
    def pr51_fresh(x):
        return jnp.tanh(x) * 3.0 + 1.0
    return jax.jit(pr51_fresh)


# -- the collector ----------------------------------------------------------

def test_a_collection_inside_a_span_is_one_host_gc_during_it(tracer):
    before = _seconds("paddle_tpu_host_gc_seconds_total", generation=2)
    with tracer.span("outer") as outer:
        gc.collect()
    got = [s for s in tracer.spans() if s.name == "host.gc"
           and s.attrs["generation"] == 2]
    assert len(got) == 1
    g = got[0]
    assert g.attrs["during"] == outer.span_id
    assert g.attrs["collected"] >= 0
    # on the tracer's clock, inside the span it interrupted, in its trace
    assert outer.start <= g.start <= g.end <= outer.end
    assert g.trace_id == outer.trace_id
    # and never a child: a step's children are its phases
    assert g.parent_id is None and g.caused_by is None
    rose = _seconds("paddle_tpu_host_gc_seconds_total",
                    generation=2) - before
    assert rose >= g.duration() > 0
    assert g.to_event()["args"]["during"] == outer.span_id


def test_a_collection_outside_any_span_is_in_the_process_trace(tracer):
    gc.collect()
    gc.collect()
    got = [s for s in tracer.spans() if s.name == "host.gc"]
    assert len(got) >= 2 and all("during" not in s.attrs for s in got)
    # one trace a process for the pauses that interrupt nothing, not one
    # a pause: a collector keeps a ring of traces
    assert len({s.trace_id for s in got}) == 1


def test_the_gc_hook_takes_no_lock_of_the_tracer(tracer):
    """The interpreter lets the collector in wherever it likes, also
    while this thread holds the ring's lock: the hook only stamps, and
    the span is made by the next reader or writer of the ring."""
    done = threading.Event()

    def collect_under_the_lock():
        with tracer._lock:
            gc.collect()
        done.set()
    t = threading.Thread(target=collect_under_the_lock, daemon=True)
    t.start()
    assert done.wait(30), "a collection under the ring's lock hung"
    assert [s for s in tracer.spans() if s.name == "host.gc"]
    tracer.clear()
    gc.collect()
    tracer.clear()              # and what was stamped goes with the ring
    assert tracer.spans() == []


# -- jax's own work ---------------------------------------------------------

def test_a_first_call_traces_lowers_and_compiles_by_name(tracer):
    f = _fresh_jit()
    x = jnp.ones((4,))
    jax.block_until_ready(x)
    tracer.clear()
    before = _jit_seconds()
    with tracer.span("outer") as outer:
        jax.block_until_ready(f(x))
    mine = [s for s in tracer.spans() if s.name.startswith("jit.")
            and "pr51_fresh" in s.attrs.get("fun_name", "")]
    assert sorted(s.name for s in mine) == ["jit.compile", "jit.lower",
                                            "jit.trace"]
    for s in mine:
        assert s.attrs["during"] == outer.span_id
        assert s.parent_id is None and s.caused_by is None
        assert outer.start <= s.start + 1e-3 and s.end <= outer.end
        assert s.duration() > 0
    # in the order jax does them
    assert [s.name for s in sorted(mine, key=lambda s: s.end)] == \
        ["jit.trace", "jit.lower", "jit.compile"]
    after = _jit_seconds()
    assert all(after[st] > before[st] for st in before)
    # the second call: nothing
    tracer.clear()
    before = _jit_seconds()
    jax.block_until_ready(f(x))
    assert [s for s in tracer.spans() if s.name.startswith("jit.")] == []
    assert _jit_seconds() == before


def test_under_the_floor_the_counters_rise_and_no_span_is_kept():
    tr = Tracer(enabled=True, bridge_jax=False)
    assert tr.install_pause_hooks(floor=3600.0) is True
    try:
        gc_before = _seconds("paddle_tpu_host_gc_seconds_total",
                             generation=2)
        jit_before = _jit_seconds()
        with tr.span("outer"):
            gc.collect()
            jax.block_until_ready(_fresh_jit()(jnp.ones((3,))))
        assert [s.name for s in tr.spans()] == ["outer"]
        assert _seconds("paddle_tpu_host_gc_seconds_total",
                        generation=2) > gc_before
        after = _jit_seconds()
        assert all(after[st] > jit_before[st] for st in jit_before)
        # the floor is the hooks' argument: calling again only sets it
        n = len(gc.callbacks)
        assert tr.install_pause_hooks(floor=0.0) is True
        assert len(gc.callbacks) == n
        gc.collect()
        assert [s.name for s in tr.spans()] == ["outer", "host.gc"]
    finally:
        tr.remove_pause_hooks()


def test_a_tracer_that_is_off_installs_nothing_and_records_nothing():
    callbacks = list(gc.callbacks)
    listeners = list(jax._src.monitoring.get_event_duration_listeners())
    tr = Tracer(enabled=False)
    assert tr.install_pause_hooks(floor=0.0) is False
    assert gc.callbacks == callbacks
    assert list(jax._src.monitoring.get_event_duration_listeners()) \
        == listeners
    with tr.span("outer"):
        gc.collect()
        jax.block_until_ready(_fresh_jit()(jnp.ones((2,))))
    assert tr.spans() == []
    tr.remove_pause_hooks()     # nothing to remove, no error
    assert gc.callbacks == callbacks


def test_the_hooks_come_off_and_go_on_again(tracer):
    n = len(gc.callbacks)
    tracer.remove_pause_hooks()
    assert len(gc.callbacks) == n - 1
    gc.collect()
    jax.block_until_ready(_fresh_jit()(jnp.ones((5,))))
    assert tracer.spans() == []
    assert tracer.install_pause_hooks(floor=0.0) is True
    assert len(gc.callbacks) == n
    gc.collect()
    assert [s.name for s in tracer.spans()] == ["host.gc"]


# -- Engine.step ------------------------------------------------------------

@pytest.mark.parametrize("where", ["admit", "record_token"])
def test_a_collection_inside_the_step_leaves_its_children_its_phases(
        engine, global_pauses, where, monkeypatch):
    sched = engine.scheduler
    inner = getattr(sched, where)

    def with_a_collection(*a, **kw):
        gc.collect()
        return inner(*a, **kw)
    monkeypatch.setattr(sched, where, with_a_collection)
    TRACER.clear()
    engine.submit(np.arange(1, 6), max_new_tokens=4)
    engine.run_until_idle()
    spans = TRACER.spans()
    busy = [s for s in spans if s.name == "engine.step"
            and not s.attrs.get("idle")]
    assert len(busy) >= 3
    by_id = {s.span_id: s for s in spans}
    for st in busy:
        assert [k.name for k in _children(spans, st)] == PHASES
    pauses = [s for s in spans if s.name == "host.gc"
              and s.attrs["generation"] == 2]
    assert len(pauses) >= len(busy) - 1
    phase = "engine.admit" if where == "admit" else "engine.emit"
    for g in pauses:
        held = by_id[g.attrs["during"]]
        assert held.name == phase
        assert held.start <= g.start and g.end <= held.end
        assert g.parent_id is None and g.caused_by is None
    # nothing compiled in a warmed engine's steps
    assert [s for s in spans if s.name == "jit.compile"
            and "during" in s.attrs] == []


def test_the_engines_histograms_are_fed_the_spans_own_lengths(engine):
    """One clock in `Engine.step` (ISSUE 51): the prefill and decode
    histograms hold the lengths of `engine.prefill` and `engine.decode`,
    no second pair of clock reads round the same calls. Since ISSUE 44
    the first is the prefill's DISPATCH."""
    eid = engine.engine_id
    hist = {n: registry.REGISTRY.get(
        f"paddle_tpu_serving_{n}_seconds").labels(engine=eid)
        for n in ("prefill", "decode_step")}
    before = {n: (h.count, h.sum) for n, h in hist.items()}
    TRACER.clear()
    for n in (5, 12):
        engine.submit(np.arange(1, n + 1), max_new_tokens=3)
    engine.run_until_idle()
    spans = TRACER.spans()
    for n, name in (("prefill", "engine.prefill"),
                    ("decode_step", "engine.decode")):
        mine = [s for s in spans if s.name == name]
        assert len(mine) == hist[n].count - before[n][0] > 0
        assert sum(s.duration() for s in mine) == pytest.approx(
            hist[n].sum - before[n][1], rel=1e-9, abs=1e-12)
    # the flight event of a prefill carries the same number
    from paddle_tpu.observability import flight
    ev = [e for e in flight.RECORDER.events("serving")
          if e.kind == "prefill" and e.attrs.get("engine") == eid][-2:]
    pre = [s for s in spans if s.name == "engine.prefill"]
    assert [e.attrs["seconds"] for e in ev] == \
        [round(s.duration(), 6) for s in pre]
    assert "DISPATCH" in registry.REGISTRY.get(
        "paddle_tpu_serving_prefill_seconds").help


def test_the_default_floor_is_a_millisecond_and_the_names_are_fixed():
    assert tracing.PAUSE_FLOOR == 1e-3
    assert sorted([f"jit.{st}" for st in tracing._JIT_STAGES.values()]
                  + ["host.gc"]) == sorted(PAUSES)
    text = registry.prometheus_text()
    assert "paddle_tpu_host_gc_seconds_total" in text
    assert "paddle_tpu_jit_seconds_total" in text

"""Structured host-side tracing: spans with trace/span ids.

The cross-tier half of the telemetry substrate (registry.py holds the
numbers; this holds the *timeline*):

  * ``span(name, **attrs)`` — context manager recording a host span
    into a bounded ring buffer; spans nest via a thread-local stack and
    children inherit their parent's ``trace_id``;
  * trace propagation — ``current_trace_id()`` reads the ambient id so
    a transport can carry it across processes (the PS wire skeleton
    carries it as ``_trace_id``, see runtime/rpc.py), and
    ``span(..., trace_id=...)`` re-roots the receiving side, so ONE
    generate request is followable frontend -> engine and
    worker -> PS server;
  * Chrome export — ``export_chrome_trace()`` emits ``trace_event``
    JSON (Perfetto / chrome://tracing), one complete event per span
    with trace/span ids in ``args``;
  * XPlane bridge — every recorded span also enters
    ``jax.profiler.TraceAnnotation``, so host spans line up with device
    traces inside a ``jax.profiler.start_trace`` window;
  * ``record(name, start, end, ...)`` — a finished span whose two stamps
    were taken elsewhere with ``TRACER.clock()`` (a queue wait starts in
    one thread's ``submit`` and ends in another's ``admit``). Every
    stamp of the tracer is ``TRACER.clock()``: a reader that wants the
    spans on another clock reads both back to back and takes the
    offset;
  * pause spans — what interrupts a thread from inside the process, on
    the same clock (``Tracer.install_pause_hooks``): ``host.gc`` for a
    pass of the collector, ``jit.trace`` / ``jit.lower`` /
    ``jit.compile`` / ``jit.cache_load`` for jax's own work on any
    jitted function. Each names the span that was open on its thread in
    the attribute ``during``, never in ``parent_id`` or ``caused_by``:
    a step's children stay its phases. Every pause is counted in the
    registry; one of ``PAUSE_FLOOR`` or longer also leaves a span.

``PADDLE_TPU_TRACE=0`` disables recording (ids still propagate so
downstream tiers keep correlating); ``PADDLE_TPU_TRACE_BRIDGE=0``
disables only the jax annotation bridge.
"""
from __future__ import annotations

import gc
import itertools
import json
import os
import sys
import threading
import time
from collections import deque

from . import registry as _obs

__all__ = ["Span", "Tracer", "TRACER", "span", "current_trace_id",
           "export_chrome_trace", "new_trace_id"]

# the span ring is bounded; overwrites used to be silent — mirror the
# flight rings' drop accounting so a reader knows the window clipped
_DROPPED = _obs.counter(
    "paddle_tpu_trace_dropped_total",
    "spans overwritten by a full trace ring")
_HIGH_WATER = _obs.gauge(
    "paddle_tpu_trace_ring_high_water",
    "max spans ever resident in the trace ring (ring size when the "
    "ring has wrapped)")


# every pause is counted here, whatever its length: the spans keep only
# those of PAUSE_FLOOR or longer
_GC_SECONDS = _obs.counter(
    "paddle_tpu_host_gc_seconds_total",
    "seconds this process spent in the garbage collector, by the "
    "generation collected", ("generation",))
_JIT_SECONDS = _obs.counter(
    "paddle_tpu_jit_seconds_total",
    "seconds jax spent on any jitted function of this process, by "
    "stage: trace (to a jaxpr), lower (to MLIR), compile (the backend, "
    "or the load from the compile cache in its place), cache_load (the "
    "retrieval alone)", ("stage",))

# A pause shorter than this is counted and leaves no span. Set-up runs
# hundreds of one-primitive programs and a busy collector makes several
# passes a second: a ring that drops one span silences every span reader
# of the run.
PAUSE_FLOOR = 1e-3

# jax.monitoring's duration events -> the stage of `jit.<stage>`
# (jax/_src/dispatch.py, jax/_src/compiler.py); the first three carry
# `fun_name`
_JIT_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}


def new_trace_id() -> str:
    return os.urandom(8).hex()


# span ids: a counter behind a prefix drawn once per process (a forked
# child draws its own), so ids stay unique across the processes whose
# spans the collector assembles into one waterfall, at no syscall a span
_span_seq = itertools.count(1)
_span_prefix = os.urandom(4).hex()


def _reseed_span_ids():
    global _span_prefix
    _span_prefix = os.urandom(4).hex()


os.register_at_fork(after_in_child=_reseed_span_ids)


def _new_span_id() -> str:
    return f"{_span_prefix}{next(_span_seq):08x}"


_annotation = None


def _trace_annotation(name):
    """`jax.profiler.TraceAnnotation(name)`; jax is imported on the first
    bridged span, not at import (the scheduler's policy layer stays
    importable without it) and not once a span."""
    global _annotation
    if _annotation is None:
        import jax
        _annotation = jax.profiler.TraceAnnotation
        TRACER._listen_to_jax()
    return _annotation(name)


class Span:
    """One span; also its own context manager (`Tracer.span` returns it
    unentered, `with` stamps it and links it to the ambient span)."""

    __slots__ = ("name", "trace_id", "span_id", "parent_id", "caused_by",
                 "start", "end", "tid", "attrs", "_tracer", "_ann")

    def __init__(self, name, trace_id, span_id, parent_id, start,
                 tid, attrs, caused_by=None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        # the ambient span when `trace_id=` re-rooted this one: a request's
        # prefill is in the request's trace, and still names the step
        # that ran it
        self.caused_by = caused_by
        self.start = start
        self.end = None
        self.tid = tid
        self.attrs = attrs
        self._tracer = None
        self._ann = None

    def duration(self) -> float | None:
        return None if self.end is None else self.end - self.start

    def __enter__(self) -> "Span":
        tr = self._tracer
        stack = tr._stack()
        parent = stack[-1] if stack else None
        if parent is None:
            self.trace_id = self.trace_id or new_trace_id()
        elif not self.trace_id or self.trace_id == parent.trace_id:
            self.trace_id = parent.trace_id
            self.parent_id = parent.span_id
        else:
            self.caused_by = parent.span_id
        self.span_id = _new_span_id()
        self.tid = threading.get_ident()
        stack.append(self)
        if tr.enabled and tr.bridge_jax:
            self._ann = _trace_annotation(self.name)
            self._ann.__enter__()
        self.start = tr.clock()
        return self

    def __exit__(self, *exc):
        tr = self._tracer
        self.end = tr.clock()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        tr._stack().pop()
        if tr.enabled:
            tr._keep(self)
        return False

    def to_event(self) -> dict:
        """One Chrome trace_event 'X' (complete) event."""
        args = {"trace_id": self.trace_id, "span_id": self.span_id}
        if self.parent_id:
            args["parent_id"] = self.parent_id
        if self.caused_by:
            args["caused_by"] = self.caused_by
        args.update(self.attrs)
        return {"name": self.name, "ph": "X", "cat": "paddle_tpu",
                "ts": round(self.start * 1e6, 3),
                "dur": round(((self.end or self.start) - self.start)
                             * 1e6, 3),
                "pid": os.getpid(), "tid": self.tid, "args": args}


# The ring's default size. A reader that finds a dropped span reads
# nothing (benchmark/readers/spans.py), so the ring has to hold what a
# serving process records between its start and a 40 s window's end: seven
# spans a decode step and one a request. At the 12 ms steps of PR 29 that
# is 23,000 spans (16,384, the size until then, dropped 3,578 of them);
# 131,072 holds steps down to 2 ms, at about 0.5 KB a span when full.
MAX_SPANS = 131072


class Tracer:
    """Bounded span recorder + thread-local trace context."""

    def __init__(self, max_spans: int = MAX_SPANS, enabled: bool | None
                 = None, bridge_jax: bool | None = None):
        if enabled is None:
            enabled = os.environ.get("PADDLE_TPU_TRACE", "1") != "0"
        if bridge_jax is None:
            bridge_jax = os.environ.get(
                "PADDLE_TPU_TRACE_BRIDGE", "1") != "0"
        self.enabled = bool(enabled)
        self.bridge_jax = bool(bridge_jax)
        # every stamp of a span is this clock's
        self.clock = time.monotonic
        self._spans: deque[Span] = deque(maxlen=max_spans)
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._high_water = 0
        # optional per-span tap (the telemetry agent): called OUTSIDE
        # the ring lock with each finished span; must never block
        self._sink = None
        # pause hooks (install_pause_hooks): None until installed
        self._pause_floor = None
        # the trace of the pauses that interrupt no span: one a process,
        # not one a pause (a collector keeps a ring of traces)
        self._pause_trace = None
        self._gc_t0 = None
        self._gc_seconds = ()
        self._jit_seconds = {}
        self._jit_listening = False
        # collections of the floor or longer, stamped by the gc hook and
        # made spans by the next `_keep` or `spans()`: the hook runs
        # wherever the interpreter lets the collector in, also while
        # this thread holds the ring's lock or the sink's, so it takes
        # neither
        self._gc_done: deque = deque()

    def set_sink(self, fn):
        """``fn(span)`` is called for every finished span (after ring
        append, outside the tracer lock). Pass None to detach. The sink
        must be cheap and non-blocking — it runs on the traced thread."""
        self._sink = fn

    # -- context --------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current_trace_id(self) -> str | None:
        st = self._stack()
        return st[-1].trace_id if st else None

    def current_span(self) -> Span | None:
        st = self._stack()
        return st[-1] if st else None

    def span(self, name: str, trace_id: str | None = None,
             **attrs) -> Span:
        """Record one host span: `with tracer.span(...) as sp`.
        ``trace_id`` re-roots the context (a request id that arrived
        over the wire; the ambient span's id is kept in ``caused_by``);
        otherwise the ambient parent's id is inherited, else a fresh one
        is minted."""
        sp = Span(name, trace_id, None, None, None, None, attrs)
        sp._tracer = self
        return sp

    def record(self, name: str, start: float, end: float,
               trace_id: str | None = None, parent_id: str | None = None,
               **attrs) -> Span | None:
        """A finished span whose stamps were taken elsewhere, both with
        ``self.clock()``. Same ring, sink and drop accounting as
        ``span()``; no profiler annotation (it cannot be backdated).
        Returns None with recording off."""
        if not self.enabled:
            return None
        sp = Span(name, trace_id or self.current_trace_id()
                  or new_trace_id(), _new_span_id(), parent_id, start,
                  threading.get_ident(), attrs)
        sp.end = end
        self._keep(sp)
        return sp

    # -- pauses -----------------------------------------------------------
    def install_pause_hooks(self, floor: float = PAUSE_FLOOR) -> bool:
        """Record what interrupts a thread from inside the process:
        `host.gc` (a `gc.callbacks` hook) and `jit.trace` / `jit.lower` /
        `jit.compile` / `jit.cache_load` (one `jax.monitoring` listener,
        registered now if jax is imported, else where the tracer first
        imports it). Every pause adds its seconds to
        `paddle_tpu_host_gc_seconds_total{generation}` or
        `paddle_tpu_jit_seconds_total{stage}`; one of `floor` seconds or
        longer is also kept as a span with the attribute `during`, the
        `span_id` of the span open on its thread. A tracer that is not
        enabled installs nothing (False). Calling again only sets the
        floor."""
        if not self.enabled:
            return False
        first = self._pause_floor is None
        self._pause_floor = float(floor)
        if first:
            self._pause_trace = new_trace_id()
            # (literal label values: the analysis' cardinality rule)
            self._gc_seconds = (_GC_SECONDS.labels(generation="0"),
                                _GC_SECONDS.labels(generation="1"),
                                _GC_SECONDS.labels(generation="2"))
            self._jit_seconds = {
                "trace": _JIT_SECONDS.labels(stage="trace"),
                "lower": _JIT_SECONDS.labels(stage="lower"),
                "compile": _JIT_SECONDS.labels(stage="compile"),
                "cache_load": _JIT_SECONDS.labels(stage="cache_load")}
            gc.callbacks.append(self._on_gc)
            if "jax" in sys.modules:
                self._listen_to_jax()
        return True

    def remove_pause_hooks(self):
        if self._pause_floor is None:
            return
        self._pause_floor = None
        gc.callbacks.remove(self._on_gc)
        if self._jit_listening:
            import jax
            jax.monitoring.unregister_event_duration_listener(self._on_jit)
            self._jit_listening = False

    def _listen_to_jax(self):
        if self._pause_floor is None or self._jit_listening:
            return
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_jit)
        self._jit_listening = True

    def _on_gc(self, phase, info):
        # no lock, no span, no ring here: see `_gc_done`
        if phase == "start":
            self._gc_t0 = self.clock()
            return
        t0, self._gc_t0 = self._gc_t0, None
        floor = self._pause_floor
        if t0 is None or floor is None:     # (un)installed under it
            return
        end = self.clock()
        gen = info.get("generation", 2)
        self._gc_seconds[gen].inc(end - t0)
        if self.enabled and end - t0 >= floor:
            self._gc_done.append((t0, end, gen, info.get("collected", 0),
                                  self.current_span(),
                                  threading.get_ident()))

    def _drain_gc(self):
        while self._gc_done:
            try:
                t0, end, gen, collected, amb, tid = self._gc_done.popleft()
            except IndexError:      # another thread took it
                return
            sp = Span("host.gc",
                      amb.trace_id if amb else self._pause_trace,
                      _new_span_id(), None, t0, tid,
                      {"generation": gen, "collected": collected,
                       **({"during": amb.span_id} if amb else {})})
            sp.end = end
            self._keep(sp)

    def _on_jit(self, event, seconds, **kw):
        stage = _JIT_STAGES.get(event)
        floor = self._pause_floor
        if stage is None or floor is None:
            return
        now = self.clock()
        self._jit_seconds[stage].inc(max(0.0, seconds))
        if seconds < floor:
            return
        attrs = {"fun_name": kw["fun_name"]} if "fun_name" in kw else {}
        amb = self.current_span()
        if amb is not None:
            attrs["during"] = amb.span_id
        self.record(f"jit.{stage}", now - seconds, now,
                    trace_id=amb.trace_id if amb else self._pause_trace,
                    **attrs)

    def _keep(self, sp: Span):
        if self._gc_done:
            self._drain_gc()
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                _DROPPED.inc()
            self._spans.append(sp)
            n = len(self._spans)
            if n > self._high_water:
                self._high_water = n
                _HIGH_WATER.set(n)
        sink = self._sink
        if sink is not None:
            try:
                sink(sp)
            except Exception:
                pass

    # -- inspection / export --------------------------------------------
    def spans(self) -> list[Span]:
        if self._gc_done:
            self._drain_gc()
        with self._lock:
            return list(self._spans)

    def clear(self):
        self._gc_done.clear()
        with self._lock:
            self._spans.clear()

    def export_chrome_trace(self, path: str | None = None) -> dict:
        """{"traceEvents": [...]} — load in Perfetto/chrome://tracing.
        Open it next to the XPlane trace of the same window: the bridge
        gives device-side TraceMe slices the same span names."""
        doc = {"traceEvents": [s.to_event() for s in self.spans()],
               "displayTimeUnit": "ms"}
        if path:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            tmp = f"{path}.tmp{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
        return doc


TRACER = Tracer()
TRACER.install_pause_hooks()
span = TRACER.span
current_trace_id = TRACER.current_trace_id
export_chrome_trace = TRACER.export_chrome_trace

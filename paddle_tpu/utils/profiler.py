"""Profiler (reference platform/profiler.h + python/paddle/fluid/profiler.py).

TPU-native: jax.profiler (XPlane) traces device + host; op-phase markers come
from the executor's jax.named_scope per op (replacing RecordEvent RAII at
framework/operator.cc:984). View with TensorBoard or Perfetto.

``RecordEvent`` also records a host span into
``paddle_tpu.observability.tracing`` (same bounded ring the serving/PS
tiers write), so marker events land in the Chrome trace export next to
the engine/rpc spans.
"""
from __future__ import annotations

import contextlib
import os
import time

import jax

from ..observability import tracing as _tracing

__all__ = ["Profiler", "profiler", "start_profiler", "stop_profiler",
           "RecordEvent", "op_profile_report"]

_trace_dir = None
_trace_started = False


# ---------------------------------------------------------------------------
# per-op aggregation (reference profiler.cc sorted event report: the
# C++ profiler times every op's Run; here the eager tracer is hooked and
# each kernel is synchronously timed — trace-accurate for dygraph, while
# jitted static steps are one fused computation by design and show up in
# the XPlane trace instead)
# ---------------------------------------------------------------------------

_op_stats: dict[str, list] = {}  # op -> [calls, total_s, max_s]
_hooked = False


def _hook_tracer():
    global _hooked
    if _hooked:
        return
    from ..fluid.dygraph import tracer as trmod
    orig = trmod.Tracer.trace_op

    def timed(self, op_type, *a, **kw):
        if _trace_dir is None:  # profiler off -> zero overhead path
            return orig(self, op_type, *a, **kw)
        t0 = time.perf_counter()
        res = orig(self, op_type, *a, **kw)
        jax.block_until_ready([t._value for lst in res.values()
                               for t in lst if t is not None])
        dt = time.perf_counter() - t0
        st = _op_stats.setdefault(op_type, [0, 0.0, 0.0])
        st[0] += 1
        st[1] += dt
        st[2] = max(st[2], dt)
        return res

    trmod.Tracer.trace_op = timed
    _hooked = True


def op_profile_report(sorted_key="total") -> str:
    """Aggregated per-op table (reference profiler.cc PrintProfiler)."""
    key = {"total": 1, "calls": 0, "max": 2,
           "ave": None}.get(sorted_key, 1)
    rows = sorted(
        _op_stats.items(),
        key=(lambda kv: kv[1][1] / max(kv[1][0], 1)) if key is None
        else (lambda kv: kv[1][key]), reverse=True)
    total = sum(v[1] for v in _op_stats.values()) or 1.0
    lines = [f"{'Op':<28}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>10}"
             f"{'Max(ms)':>10}{'Ratio':>8}"]
    for op, (calls, tot, mx) in rows:
        lines.append(
            f"{op:<28}{calls:>8}{tot * 1e3:>12.3f}"
            f"{tot / calls * 1e3:>10.3f}{mx * 1e3:>10.3f}"
            f"{tot / total:>8.1%}")
    return "\n".join(lines)


def start_profiler(state="All", tracer_option="Default",
                   trace_dir="/tmp/paddle_tpu_trace"):
    global _trace_dir, _trace_started
    _op_stats.clear()
    _hook_tracer()
    _trace_dir = trace_dir
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)
    _trace_started = True


def stop_profiler(sorted_key=None, profile_path=None):
    global _trace_dir, _trace_started
    if _trace_started:
        jax.profiler.stop_trace()
    _trace_started = False
    out = _trace_dir
    _trace_dir = None
    if _op_stats:
        report = op_profile_report(sorted_key or "total")
        if profile_path:
            with open(profile_path, "w") as f:
                f.write(report + "\n")
        else:
            print(report, flush=True)
    return out


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path=None,
             tracer_option="Default"):
    """profile_path is where the REPORT file goes (reference
    fluid/profiler.py contract); the XPlane trace always lands in a trace
    directory."""
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


class RecordEvent:
    """Host event marker (reference platform/profiler.h:126).

    Backed by observability.tracing: records a host span (Chrome trace
    export) AND enters jax.profiler.TraceAnnotation, so the marker
    shows up in the XPlane device trace too."""

    def __init__(self, name: str):
        self.name = name
        self._cm = None

    def __enter__(self):
        self._cm = _tracing.span(self.name)
        self._cm.__enter__()
        return self

    def __exit__(self, *exc):
        if self._cm is None:
            return False
        cm, self._cm = self._cm, None
        return cm.__exit__(*(exc or (None, None, None)))

    begin = __enter__

    def end(self):
        self.__exit__(None, None, None)


class Profiler:
    """2.0-style paddle.profiler.Profiler."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 trace_dir="/tmp/paddle_tpu_trace"):
        self.trace_dir = trace_dir
        self._running = False

    def start(self):
        start_profiler(trace_dir=self.trace_dir)
        self._running = True

    def stop(self):
        if self._running:
            stop_profiler()
            self._running = False

    def step(self):
        pass

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    def summary(self, **kw):
        return f"trace written to {self.trace_dir} (view with TensorBoard)"

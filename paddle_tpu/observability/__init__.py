"""paddle_tpu.observability — unified runtime telemetry + postmortem.

One substrate replacing the fragmented per-tier stat dicts (serving
engine p50/p99 under a stats lock, PSClient retry counters, autobench
stderr prints, the disconnected jax.profiler wrapper):

  * ``registry`` — thread-safe labeled counters / gauges / fixed-bucket
    histograms with Prometheus-text + JSON exposition and per-process
    file dumps (``PADDLE_TPU_METRICS_DIR``) aggregatable across a
    ``launch.py`` job;
  * ``tracing`` — host spans with trace/span ids, Chrome trace_event
    export, a jax.profiler.TraceAnnotation bridge (host spans line up
    with XPlane device traces), and a trace-id field carried in the PS
    RPC wire skeleton so one request is followable across processes;
  * ``flight`` — the black box: bounded per-tier event rings (request
    lifecycles, RPC calls, PS push/snapshot/WAL commits, checkpoint
    writer transitions, compile events), cheap enough to stay on in
    production, dumped whole into postmortem bundles;
  * ``watchdog`` — progress-token stall detection: each tier registers
    a counter it must advance; no progress past a deadline raises
    ``paddle_tpu_watchdog_*`` metrics, writes a bundle, and can
    re-raise SIGTERM for the launch.py respawn path;
  * ``debug`` — atomic, CRC-manifested postmortem bundle directories
    (``PADDLE_TPU_DEBUG_DIR`` / ``launch.py --debug_dir``), written on
    watchdog fire, unhandled exception, SIGTERM, and on demand via the
    ``debug_dump`` verb of the serving frontend and PS servers.

Scrape points: the serving frontend and every PS server answer
``metrics`` (Prometheus text) and ``debug_dump`` (full bundle) verbs
(docs/OBSERVABILITY.md, docs/DEBUGGING.md).

Quick use:

    from paddle_tpu import observability as obs
    reqs = obs.counter("paddle_tpu_myapp_requests_total", "requests")
    with obs.span("myapp.handle", route="/gen"):
        reqs.inc()
        obs.flight.record("myapp", "handled", route="/gen")
    print(obs.prometheus_text())
    obs.write_bundle("/tmp/debug", reason="manual")

``obs.set_enabled(False)`` (or ``PADDLE_TPU_TELEMETRY=0``) turns every
metric write, span record and flight event into a cheap no-op.
"""
from __future__ import annotations

import atexit
import os
import socket

from . import agent, alerts, collector, debug, flight, meter, perf, \
    registry, timeseries, tracing, watchdog
from .agent import TelemetryAgent, publish_event
from .alerts import AlertManager, AlertRule
from .collector import TelemetryCollector, telemetry_dispatch
from .meter import METER, UsageMeter, usage_report
from .timeseries import TimeSeriesDB
from .debug import collect, load_bundle, write_bundle
from .flight import RECORDER
from .registry import (REGISTRY, Counter, Gauge, Histogram, MetricError,
                       MetricsRegistry, aggregate_dir, aggregate_dumps,
                       counter, dump_to_file, gauge, histogram,
                       prometheus_text, to_dict)
from .tracing import (TRACER, Span, Tracer, current_trace_id,
                      export_chrome_trace, new_trace_id, span)
from .watchdog import WATCHDOG

__all__ = [
    "registry", "tracing", "flight", "watchdog", "debug",
    "agent", "collector", "perf",
    "timeseries", "alerts", "meter",
    "TelemetryAgent", "TelemetryCollector",
    "telemetry_dispatch", "publish_event",
    "TimeSeriesDB", "AlertManager", "AlertRule",
    "UsageMeter", "METER", "usage_report",
    "REGISTRY", "MetricsRegistry", "MetricError",
    "Counter", "Gauge", "Histogram",
    "counter", "gauge", "histogram",
    "prometheus_text", "to_dict", "dump_to_file",
    "aggregate_dumps", "aggregate_dir",
    "TRACER", "Tracer", "Span", "span", "current_trace_id",
    "new_trace_id", "export_chrome_trace",
    "RECORDER", "WATCHDOG",
    "collect", "write_bundle", "load_bundle",
    "set_enabled", "enabled",
]


def set_enabled(on: bool):
    """Master switch: metric writes, span recording AND flight events
    (trace ids still propagate so cross-process correlation survives a
    disabled tier)."""
    REGISTRY.set_enabled(on)
    TRACER.enabled = bool(on)
    RECORDER.set_enabled(on)


def enabled() -> bool:
    return REGISTRY.enabled


def _postmortem_dump(reason: str):
    """Evidence dump for process-death paths. Into the metrics dir:
    the registry JSON plus the trace ring and flight rings (each a
    per-process file the offline aggregator can sit next to). Into the
    debug dir: one full CRC-manifested bundle."""
    d = os.environ.get("PADDLE_TPU_METRICS_DIR")
    if d:
        tag = f"{socket.gethostname()}_{os.getpid()}"
        try:
            REGISTRY.dump_to_file()
        except Exception:
            pass
        try:
            TRACER.export_chrome_trace(
                os.path.join(d, f"trace_{tag}.json"))
        except Exception:
            pass
        try:
            RECORDER.dump_to_file(
                os.path.join(d, f"flight_{tag}.json"))
        except Exception:
            pass
    debug.try_write_bundle(reason)


if os.environ.get("PADDLE_TPU_METRICS_DIR"):
    # per-process dump at exit: each launch.py child leaves one
    # metrics_<host>_<pid>.json for registry.aggregate_dir
    @atexit.register
    def _dump_metrics_at_exit():
        try:
            REGISTRY.dump_to_file()
        except Exception:
            pass


if os.environ.get("PADDLE_TPU_METRICS_DIR") \
        or os.environ.get("PADDLE_TPU_DEBUG_DIR"):
    # SIGTERM does NOT run atexit hooks, and that is exactly how
    # launch.py stops PS servers (and any survivors after a failure or
    # a hung-rank teardown): dump the metrics + trace ring + flight
    # rings (+ a debug bundle when PADDLE_TPU_DEBUG_DIR is set), then
    # die with the default disposition so the exit code stays 143.
    # Installed only over the DEFAULT handler — an app with its own
    # SIGTERM logic keeps it (and can call _postmortem_dump itself).
    def _install_sigterm_dump():
        import signal
        import threading
        if threading.current_thread() is not threading.main_thread():
            return
        if signal.getsignal(signal.SIGTERM) != signal.SIG_DFL:
            return

        def _on_term(signum, frame):
            # the handler interrupts an arbitrary main-thread frame,
            # which may HOLD one of the non-reentrant locks the dump
            # needs (flight ring, a registry child, a scheduler lock
            # behind a requests provider). A deadlocked dump must cost
            # a bounded wait, not the exit: arm a hard-exit escalation
            # FIRST, so the process still dies 143 with whatever
            # evidence made it to disk.
            debug.arm_hard_exit(name="sigterm-dump-escalate")
            _postmortem_dump("sigterm")
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
            os.kill(os.getpid(), signal.SIGTERM)

        signal.signal(signal.SIGTERM, _on_term)

    try:
        _install_sigterm_dump()
    except Exception:
        pass


if os.environ.get("PADDLE_TPU_DEBUG_DIR"):
    # unhandled exceptions (main thread or any worker thread) leave a
    # bundle behind before the traceback prints
    try:
        debug.install_crash_hooks()
    except Exception:
        pass


if os.environ.get("PADDLE_TPU_WATCHDOG", "") not in ("", "0"):
    # opt-in background stall polling; tiers register their progress
    # tokens unconditionally (registration is free), the thread only
    # runs when a job asks for it
    try:
        WATCHDOG.start()
    except Exception:
        pass


# opt-in per-process telemetry agent: PADDLE_TPU_TELEMETRY_COLLECTOR
# (launch.py --telemetry sets it for every child) arms a streamer to
# the fleet collector — spans/flight/metrics/events, one daemon
# sender thread, never in a serving path
try:
    agent.maybe_start_from_env()
except Exception:
    pass

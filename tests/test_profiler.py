"""Direct coverage for utils/profiler.py: start/stop wrappers and
RecordEvent."""
import types

import jax
import pytest

from paddle_tpu.observability import tracing as obs_tracing
from paddle_tpu.utils import profiler as P


@pytest.fixture(autouse=True)
def _reset_profiler_state():
    yield
    P._trace_dir = None
    P._trace_started = False
    P._op_stats.clear()


def test_start_stop_profiler_round_trip(monkeypatch, tmp_path):
    calls = []
    fake = types.SimpleNamespace(
        start_trace=lambda d: calls.append(("start", d)),
        stop_trace=lambda: calls.append(("stop",)),
        TraceAnnotation=jax.profiler.TraceAnnotation)
    monkeypatch.setattr(jax, "profiler", fake)
    d = str(tmp_path / "trace")
    P.start_profiler(trace_dir=d)
    assert calls == [("start", d)]
    out = P.stop_profiler()
    assert calls == [("start", d), ("stop",)] and out == d
    # stop again: no second stop_trace (no dangling start)
    P.stop_profiler()
    assert calls == [("start", d), ("stop",)]


def test_record_event_lands_in_trace_export():
    obs_tracing.TRACER.clear()
    with P.RecordEvent("op_phase_marker"):
        pass
    names = [s.name for s in obs_tracing.TRACER.spans()]
    assert "op_phase_marker" in names
    ev = P.RecordEvent("explicit")
    ev.begin()
    ev.end()
    assert "explicit" in [s.name for s in obs_tracing.TRACER.spans()]
    # exit without enter is inert
    P.RecordEvent("never_entered").end()


def test_profiler_context_and_report(monkeypatch, tmp_path, capsys):
    monkeypatch.setattr(jax, "profiler", types.SimpleNamespace(
        start_trace=lambda d: None, stop_trace=lambda: None))
    P._op_stats.clear()
    P._op_stats["matmul"] = [2, 0.004, 0.003]
    P._op_stats["relu"] = [4, 0.001, 0.0005]
    report = P.op_profile_report("total")
    lines = report.splitlines()
    assert "Op" in lines[0] and "matmul" in lines[1]  # sorted by total
    path = tmp_path / "profile.txt"
    with P.profiler(profile_path=str(path)):
        # start_profiler cleared the stats; seed inside the window so
        # stop_profiler writes the report file
        P._op_stats["matmul"] = [2, 0.004, 0.003]
    assert "matmul" in path.read_text()  # report written to profile_path

    prof = P.Profiler(trace_dir=str(tmp_path / "p2"))
    with prof:
        prof.step()
    assert "trace" in prof.summary()

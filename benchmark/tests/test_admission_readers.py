"""The readers of the admission path's own account (readers/admission.py,
ISSUE 34) on hand-made spans whose answers are plain, recorded through the
tracer as the program records them (stamps on the tracer's clock, the
window on the harness's), and the rehearsal's list of what a traced run of
a cell would report."""
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark.readers import admission as reader
from benchmark.readers import spans as span_reader
from paddle_tpu.observability.tracing import TRACER

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PROGRAM_BEHIND = 77.5       # harness clock minus the program's, seconds
H0 = 3000.0                 # the window's start on the harness's clock
NEW = ["prefill_time_share", "prefill_padding_share", "decode_ahead_share",
       "host_build_transfer_ms_p50", "wait_readback_ms_p50"]


def at(t):
    return t - PROGRAM_BEHIND


def _step(t0, no, prefills=(), stamps=True, of_step=True, ahead=True,
          idle=False, transfer=0.0008, readback=0.0002):
    """One `engine.step` from t0 (harness clock): admit holds `prefills`
    ((prompt_len, cached, bucket, seconds) each), build 2 ms of which the
    last `transfer`, dispatch
    0.5 ms, wait 10 ms of which the last `readback`, emit 0.5 ms. An idle
    step is its admit alone. Returns the step's end."""
    admit = 0.0005 + sum(p[3] for p in prefills)
    t1 = t0 + admit + (0.0 if idle else 0.002 + 0.0105 + 0.0005)
    st = TRACER.record("engine.step", at(t0), at(t1), step=no,
                       active=0 if idle else 8, admitted=len(prefills),
                       **({"idle": True} if idle else {}))
    kid = lambda name, a, b, parent=st, **kw: TRACER.record(
        name, at(a), at(b), trace_id=st.trace_id, parent_id=parent.span_id,
        **kw)
    adm = kid("engine.admit", t0, t0 + admit)
    t = t0 + 0.0005
    for plen, cached, bucket, secs in prefills:
        p = TRACER.record("engine.prefill", at(t), at(t + secs),
                          trace_id=f"r{no}-{plen}", prompt_len=plen,
                          cached_tokens=cached, bucket=bucket)
        p.caused_by = adm.span_id
        t += secs
    if idle:
        return t1
    b0 = t0 + admit
    kid("engine.build", b0, b0 + 0.002,
        **({"filled": at(b0 + 0.002 - transfer)} if stamps else {}))
    d0 = b0 + 0.002
    dec = kid("engine.decode", d0, d0 + 0.0105, active=8, ahead=ahead)
    kid("engine.dispatch", d0, d0 + 0.0005, parent=dec)
    w1 = d0 + 0.0105
    kid("engine.wait", d0 + 0.0005, w1, parent=dec,
        of_step=no - 1 if of_step else None,
        **({"ready": at(w1 - readback)} if stamps and of_step else {}))
    kid("engine.emit", w1, t1)
    return t1


@pytest.fixture
def ring(monkeypatch):
    # the counter is the process's: an earlier test may have raised it
    monkeypatch.setattr(span_reader, "_dropped", lambda: 0.0)
    monkeypatch.setattr(TRACER, "clock",
                        lambda: time.perf_counter() - PROGRAM_BEHIND)
    monkeypatch.setattr(TRACER, "enabled", True)
    TRACER.clear()


def _window(stamps=True):
    """A warm-up step before the window, then in it: a step that admits a
    padded prompt (300 tokens in a bucket of 512, 20 ms) and a cached
    tail (700 of 1,000 cached, the other 300 in 512, 12 ms) and whose
    decode is therefore not ahead; three bare steps; the first decode
    after nothing (no tokens to read); an idle step that only admits
    (100 in 128, 5 ms); and a step that ends after the window."""
    t = _step(H0 - 0.5, 0, prefills=[(64, 0, 64, 0.3)], stamps=stamps)
    t = H0 + 0.001
    t = _step(t, 1, prefills=[(300, 0, 512, 0.020), (1000, 700, 512, 0.012)],
              ahead=False, stamps=stamps, transfer=0.0012, readback=0.0004)
    for no in (2, 3, 4):
        t = _step(t, no, stamps=stamps)
    t = _step(t, 5, of_step=False, ahead=False, stamps=stamps)
    t = _step(t, 6, prefills=[(100, 0, 128, 0.005)], idle=True,
              stamps=stamps)
    end = t + 1e-4
    _step(t + 0.001, 7, prefills=[(9, 0, 4096, 0.5)], stamps=stamps)
    return {"kind": "serve", "window": (H0, end)}


def test_prefill_time_share_counts_idle_steps_and_the_window_alone(ring):
    run = _window()
    # the admitting step, four of 13.5 ms, the idle one: not the warm-up's
    # 0.3 s before the window nor the 0.5 s that end after it
    stepped = (0.0325 + 0.013) + 4 * 0.0135 + 0.0055
    assert reader.prefill_time_share(run) == pytest.approx(
        100 * (0.020 + 0.012 + 0.005) / stepped, rel=1e-6)
    # a window with steps and no admission reads 0, not nothing
    steps = [s for s in span_reader.program_spans(run)
             if s["name"] == "engine.step"]
    bare = {"kind": "serve", "window": (steps[2]["start"] + 1e-5,
                                        steps[4]["end"] + 1e-5)}
    assert reader.prefill_time_share(bare) == 0.0
    assert reader.prefill_padding_share(bare) is None


def test_prefill_padding_share_counts_a_cached_tail_by_what_was_asked(ring):
    run = _window()
    # asked 300 + (1000 - 700) + 100 of buckets 512 + 512 + 128
    assert reader.prefill_padding_share(run) == pytest.approx(
        100 * (1 - 700 / 1152))
    assert 0 <= reader.prefill_padding_share(run) <= 100


def test_decode_ahead_share_is_false_where_the_admission_drained(ring):
    run = _window()
    # five dispatched decodes: the admitting step's and the first after
    # nothing are not ahead, the three bare steps' are
    assert reader.decode_ahead_share(run) == pytest.approx(60.0)


def test_the_two_stamp_readers_take_the_tail_of_their_spans(ring):
    run = _window()
    # build: 1.2 ms on the admitting step, 0.8 on the other four
    assert reader.host_build_transfer_ms_p50(run) == pytest.approx(
        0.8, abs=1e-6)
    # wait: four had a decode to read (0.4, 0.2, 0.2, 0.2), one had none
    assert reader.wait_readback_ms_p50(run) == pytest.approx(0.2, abs=1e-6)
    assert span_reader.phase_self_ms_p50(run, "engine.build") == \
        pytest.approx(2.0, abs=1e-3)


def test_a_tail_is_a_difference_on_one_clock(ring, monkeypatch):
    """A stamp is brought over by the offset `program_spans` gave the
    span's own end, not by a second reading of the two clocks: a reading
    that is 20 us out (a preemption between the clocks) moves the window's
    edge by as much and no tail at all."""
    monkeypatch.setattr(span_reader, "clock_offset",
                        lambda _tracer: PROGRAM_BEHIND + 2e-5)
    run = _window()
    assert reader.host_build_transfer_ms_p50(run) == pytest.approx(
        0.8, abs=1e-6)
    assert reader.wait_readback_ms_p50(run) == pytest.approx(0.2, abs=1e-6)
    # and a ring that no longer holds any of the run's spans reads nothing
    TRACER.clear()
    run.pop("_span_offset")
    assert reader.wait_readback_ms_p50(run) is None


def test_a_parent_without_the_stamps_reads_what_it_has(ring):
    """The parent of ISSUE 34 records the spans and their older attributes
    and no stamp: the two stamp readers find nothing, the three others
    read as they do on the change. A program with no such span at all
    and a training run read nothing anywhere."""
    run = _window(stamps=False)
    assert reader.host_build_transfer_ms_p50(run) is None
    assert reader.wait_readback_ms_p50(run) is None
    assert reader.prefill_padding_share(run) == pytest.approx(
        100 * (1 - 700 / 1152))
    assert reader.decode_ahead_share(run) == pytest.approx(60.0)
    assert reader.prefill_time_share(run) is not None
    fns = [getattr(reader, n) for n in NEW]
    TRACER.clear()
    TRACER.record("engine.decode", at(H0 + 1.0), at(H0 + 2.0))  # PR 23's
    old = {"kind": "serve", "window": (H0, H0 + 5.0)}
    assert [fn(dict(old)) for fn in fns] == [None] * 5
    assert [fn({"kind": "train", "window": (H0, H0 + 5.0)})
            for fn in fns] == [None] * 5


def test_a_risen_drop_counter_gives_none(ring, monkeypatch):
    run = _window()
    monkeypatch.setattr(span_reader, "_dropped", lambda: 2.0)
    assert [getattr(reader, n)(dict(run)) for n in NEW] == [None] * 5


def test_every_admission_metric_has_its_file_and_its_cells():
    """Each of the five is declared, with a file whose reader resolves,
    in cells that report the metric it moves. Nothing here counts the
    benchmark's metrics or cells, or says where in the list these stand:
    a later PR appends beside them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    reports = {m["name"]: m.get("workloads", ()) for m in bench["end_to_end"]}
    for name in NEW:
        m = by_name[name]
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        mod, fn = spec["reader"].split(":")
        assert mod == "admission" and callable(getattr(reader, fn))
        assert (spec["unit"], spec["layer"], spec["moves"], spec["source"]) \
            == (m["unit"], m["layer"], m["moves"], m["source"])
        assert m["workloads"] and \
            set(m["workloads"]) <= set(reports[m["moves"]])
        assert (spec.get("max") == 100) == (m["unit"] == "%")


def test_rehearsal_lists_the_admission_metrics():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearse.py"),
         "--workload", "gpt_1p3b_serve.decode_closed64", "--trace", "1"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(NEW) <= set(line["would_report"])

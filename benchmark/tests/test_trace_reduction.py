"""The reduction gives known busy/idle, kernel time, span cover and gap
attribution: on a hand-made trace whose answers are plain, and on a small
trace recorded on the chip (tests/data/)."""
import json
import os

import pytest

from benchmark.lib.trace import Reduced, covered, union

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _made():
    # two steps of 100 us on the host; the device works 10-60 and 110-190,
    # a kernel takes 20 of each step, chip 1 mirrors chip 0 shifted by 5
    dev = lambda off: {"name": f"/device:TPU:{0 if not off else 1}", "lines": [
        {"name": "XLA Ops", "events": [
            ["%fusion.1 = fusion", 10_000 + off, 30_000],
            ["%k = custom-call:tpu_custom_call - bf16[4,2,8]", 40_000 + off,
             20_000],
            ["%all-reduce.1 = all-reduce", 110_000 + off, 30_000],
            ["%fusion.1 = fusion", 130_000 + off, 40_000],
            ["%k = custom-call:tpu_custom_call - bf16[4,2,8]", 170_000 + off,
             20_000]]},
        {"name": "XLA Modules", "events": [
            ["jit_decode(123)", 10_000 + off, 50_000],
            ["jit_decode(123)", 110_000 + off, 80_000]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ["bench.step", 0, 100_000], ["bench.step", 100_000, 100_000],
        ["something else", 5, 10]]}]}
    return {"planes": [dev(0), dev(5_000), host]}


def test_known_answers_on_a_made_trace():
    r = Reduced(_made())
    assert r.window_s == pytest.approx(200e-6)
    assert r.busy_s == pytest.approx(130e-6)            # 50 + 80, both chips
    assert r.idle_share() == pytest.approx(1 - 130 / 200)
    assert r.op_seconds(r"custom-call.*bf16\[4,2,8\]") == pytest.approx(40e-6)
    assert r.op_count("jit_decode", "modules") == 2
    assert r.op_seconds("jit_decode", "modules") == pytest.approx(130e-6)
    assert r.span_count("bench.step") == 2
    assert r.span_host_share("bench.step") == pytest.approx(70 / 200)
    gaps = r.idle_gaps(3)
    # chip 0: idle 60-110 (covers the step boundary: its middle, 85, lies
    # in the first step), 0-10 and 190-200
    assert gaps[0] == ["bench.step", pytest.approx(50e-6)]
    assert [g[1] for g in gaps[1:]] == [pytest.approx(10e-6)] * 2
    top = r.top_ops(2)
    assert top[0][0].startswith("%fusion.1") and top[0][1] == \
        pytest.approx(70e-6)
    # the all-reduce runs 110-140; compute overlaps it from 130: 20 exposed
    assert r.collective_exposed_share() == pytest.approx(20 / 200)
    assert set(r.breakdown()) == {"device_ops", "idle_gaps"}


def test_interval_helpers():
    assert union([[5, 7], [1, 3], [2, 4]]) == [[1, 4], [5, 7]]
    assert covered([[0, 10]], [[2, 4], [8, 12]]) == 4


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(DATA) if f.endswith(".json")) if os.path.isdir(DATA)
    else [])
def test_known_answers_on_a_recorded_trace(name):
    with open(os.path.join(DATA, name)) as f:
        doc = json.load(f)
    r = Reduced(doc["trace"])
    want = doc["expect"]
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert r.span_count(want["span"]) == want["spans"]
    assert r.op_seconds(want["kernel"]) == pytest.approx(
        want["kernel_s"], rel=1e-9)
    assert r.op_count(want["kernel"]) == want["kernel_calls"]
    assert [g[0] for g in r.idle_gaps(3)] == want["gap_names"]
    assert r.span_host_share(want["span"]) == pytest.approx(
        want["host_share"], rel=1e-9)


def test_nested_events_count_their_own_time_only():
    from benchmark.lib.trace import self_times
    got = dict(self_times([["while", 0, 100], ["a", 10, 30], ["b", 50, 40],
                           ["alone", 200, 5]]))
    assert got == {"while": 30, "a": 30, "b": 40, "alone": 5}


def test_slices_are_cut_at_step_ends():
    from benchmark.lib.stats import slice_rates
    # steps of 0.3 s that emit 3 units each: every slice reads 10 a second,
    # though a 2 s slice holds six steps and two thirds
    steps = [(0.3 * (i + 1), 3) for i in range(40)]
    rates = slice_rates(steps, 0.0, 12.0, min_slice=2.0)
    assert len(rates) == 6
    assert all(abs(r - 10.0) < 1e-9 for r in rates)

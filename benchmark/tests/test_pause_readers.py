"""The readers of the pause spans and of the window's stalls
(readers/pauses.py, ISSUE 51) on hand-made spans whose answers are plain,
the new metric files, and the rehearsal's list of what a traced run of a
serving and of a training cell would report."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.readers import pauses, spans as span_reader

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
STALLS = ["stall_time_share", "stall_longest_ms", "stall_wait_ms",
          "stall_host_ms", "host_pause_ms"]
SETUP = ["setup_trace_lower_s", "setup_compile_load_s"]
H0 = 1000.0                 # the window's start
BARE = dict(admit=0.0003, build=0.0007, transfer=0.0004, dispatch=0.0007,
            wait=0.0060, readback=0.0004, emit=0.0009)


class Ring:
    """Spans as `program_spans` hands them on: dicts on one clock."""

    def __init__(self):
        self.spans, self.t, self.n = [], H0, 0

    def add(self, name, start, end, parent=None, caused_by=None, **attrs):
        self.n += 1
        sp = {"name": name, "start": start, "end": end,
              "span_id": f"s{self.n}", "parent_id": parent,
              "caused_by": caused_by, "trace_id": "t", "attrs": attrs}
        self.spans.append(sp)
        return sp

    def step(self, prefills=(), compiled=False, **longer):
        """One `engine.step` at the ring's clock: the bare parts, each
        prefill adding a millisecond a 256 tokens to the wait, and
        `longer` seconds added to a part."""
        d = dict(BARE)
        d["wait"] += sum(b / 256 * 1e-3 for b in prefills)
        for part, extra in longer.items():
            d[part] += extra
        t0 = self.t
        st = self.add("engine.step", t0, t0 + sum(d.values()), step=self.n)
        t = t0
        admit = self.add("engine.admit", t, t + d["admit"],
                         parent=st["span_id"])
        for b in prefills:
            self.add("engine.prefill", t + 1e-5, t + 2e-5,
                     caused_by=admit["span_id"], bucket=b,
                     **({"compiled": True} if compiled else {}))
        t += d["admit"]
        self.add("engine.build", t, t + d["build"] + d["transfer"],
                 parent=st["span_id"], filled=t + d["build"])
        t += d["build"] + d["transfer"]
        dec_end = t + d["dispatch"] + d["wait"] + d["readback"]
        dec = self.add("engine.decode", t, dec_end, parent=st["span_id"])
        self.add("engine.dispatch", t, t + d["dispatch"],
                 parent=dec["span_id"])
        t += d["dispatch"]
        self.add("engine.wait", t, dec_end, parent=dec["span_id"],
                 ready=t + d["wait"])
        self.add("engine.emit", dec_end, dec_end + d["emit"],
                 parent=st["span_id"])
        self.t = st["end"] + 1e-5
        return st

    def run(self, kind="serve"):
        self.spans.sort(key=lambda s: s["start"])
        return {"kind": kind, "window": (H0, self.t),
                "_program_spans": self.spans, "_span_offset": 0.0}


def _quiet(ring, n=40):
    """A window of bare steps, every eighth with a prefill of 1,024."""
    for k in range(n):
        ring.step(prefills=(1024,) if k % 8 == 3 else ())


def _gc(ring, start, ms, during=None):
    return ring.add("host.gc", start, start + 1e-3 * ms, generation=2,
                    collected=0, **({"during": during} if during else {}))


def _read(run):
    return {m: getattr(pauses, m)(run) for m in STALLS}


def test_steps_that_carry_a_prefill_are_no_stalls_whatever_their_length():
    ring = Ring()
    _gc(ring, H0 - 5.0, 3.0)            # the program records pauses
    _quiet(ring)
    # one of each of three rarer kinds: 0.25 s, 0.5 s and both
    for _ in range(2):
        ring.step(prefills=(65536,))
        ring.step(prefills=(131072,))
    ring.step(prefills=(65536, 131072))
    _quiet(ring, 8)
    run = ring.run()
    got = pauses.account(run)
    longest = max(s["end"] - s["start"] for s in ring.spans
                  if s["name"] == "engine.step")
    assert longest > 0.7 and got["stalled"] == [] \
        and got["unpredicted"] == 0
    assert got["steps"] == 53
    assert _read(run) == dict.fromkeys(STALLS, 0.0)
    # the older reading counts every one of them
    assert span_reader.stall_steps(run, 3) >= 5


@pytest.mark.parametrize("part, wait, host", [
    ("wait", 300.0, 0.0), ("build", 0.0, 300.0), ("transfer", 0.0, 300.0),
    ("emit", 0.0, 300.0)])
def test_a_stall_reads_its_length_in_the_part_that_held_it(part, wait,
                                                           host):
    ring = Ring()
    _gc(ring, H0 - 5.0, 3.0)
    _quiet(ring, 20)
    ring.step(**{part: 0.3})
    _quiet(ring, 20)
    run = ring.run()
    got = _read(run)
    assert got["stall_wait_ms"] == pytest.approx(wait, abs=1e-6)
    assert got["stall_host_ms"] == pytest.approx(host, abs=1e-6)
    assert got["stall_longest_ms"] == pytest.approx(300.0, abs=1e-6)
    # the excess over the window
    window = run["window"][1] - run["window"][0]
    assert got["stall_time_share"] == pytest.approx(100 * 0.3 / window)
    (one,) = pauses.account(run)["stalled"]
    assert one["kind"] == [] and one["by_part_ms"][part] == \
        pytest.approx(300.0, abs=1e-6)
    assert got["host_pause_ms"] == 0.0


def test_a_stalled_step_with_a_prefill_is_held_against_its_own_kind():
    ring = Ring()
    _gc(ring, H0 - 5.0, 3.0)
    _quiet(ring, 48)                    # six steps of kind (1024,)
    ring.step(prefills=(1024,), wait=0.12)
    ring.step(prefills=(4096,), build=0.2)   # a kind of its own: one step
    run = ring.run()
    got = pauses.account(run)
    assert [s["kind"] for s in got["stalled"]] == [[1024]]
    assert got["stalled"][0]["excess_ms"] == pytest.approx(120.0, abs=1e-6)
    assert got["unpredicted"] == 1      # left out, and counted
    assert got["medians"]["[]"] == pytest.approx(1e3 * sum(BARE.values()))


def test_a_compiled_step_is_set_up_and_not_a_stall():
    ring = Ring()
    _gc(ring, H0 - 5.0, 3.0)
    _quiet(ring, 10)
    ring.step(prefills=(1024,), compiled=True, wait=2.0)
    _quiet(ring, 10)
    assert pauses.account(ring.run())["stalled"] == []


def test_pauses_inside_the_steps_are_summed_and_named():
    ring = Ring()
    _quiet(ring, 12)
    st = ring.step(build=0.2)
    build = next(s for s in ring.spans if s["name"] == "engine.build"
                 and s["parent_id"] == st["span_id"])
    _gc(ring, build["start"] + 0.01, 150.0, during=build["span_id"])
    ring.add("jit.compile", build["start"] + 0.17, build["start"] + 0.19,
             fun_name="jit(convert_element_type)",
             during=build["span_id"])
    _quiet(ring, 12)
    _gc(ring, ring.t + 1.0, 40.0)       # after the window's last step
    run = ring.run()
    assert pauses.host_pause_ms(run) == pytest.approx(170.0, abs=1e-6)
    (one,) = pauses.account(run)["stalled"]
    assert one["host_ms"] == pytest.approx(200.0, abs=1e-6)
    assert [(p["name"], round(p["ms"]), p["during"])
            for p in one["pauses"]] == [
        ("host.gc", 150, "engine.build"),
        ("jit.compile", 20, "engine.build")]
    assert one["pauses"][1]["fun_name"] == "jit(convert_element_type)"


def test_set_up_is_what_jax_did_before_the_window_a_moment_once():
    ring = Ring()
    t = H0 - 50.0
    # a trace of 4 s that holds an inner trace and a gate's trial compile
    ring.add("jit.trace", t, t + 4.0, fun_name="decode")
    ring.add("jit.trace", t + 0.5, t + 1.0, fun_name="inner")
    ring.add("jit.compile", t + 2.0, t + 3.0, fun_name="jit(trial)")
    ring.add("jit.lower", t + 4.0, t + 5.5, fun_name="jit(decode)")
    # a compile served from the cache: the load lies inside it
    ring.add("jit.compile", t + 5.5, t + 6.5, fun_name="jit(decode)")
    ring.add("jit.cache_load", t + 5.6, t + 6.4)
    ring.add("jit.cache_load", t + 8.0, t + 8.25)
    _quiet(ring, 8)
    # the reference compiles after the window: not set-up
    ring.add("jit.compile", ring.t + 2.0, ring.t + 9.0, fun_name="jit(ref)")
    run = ring.run()
    assert pauses.setup_trace_lower_s(run) == pytest.approx(4.5)
    assert pauses.setup_compile_load_s(run) == pytest.approx(2.25)
    # a trainer has no window: set-up ends at its last `train.step`
    train = Ring()
    train.add("jit.lower", H0 - 9.0, H0 - 7.0, fun_name="jit(step)")
    train.add("jit.compile", H0 - 7.0, H0 - 2.0, fun_name="jit(step)")
    for k in range(4):
        train.add("train.step", H0 + k, H0 + k + 0.01, step=k)
    train.add("jit.compile", H0 + 20.0, H0 + 30.0, fun_name="jit(ref)")
    run = train.run(kind="train")
    del run["window"]
    assert pauses.setup_trace_lower_s(run) == pytest.approx(2.0)
    assert pauses.setup_compile_load_s(run) == pytest.approx(5.0)
    assert pauses.stall_time_share(run) is None


def test_a_ring_that_dropped_a_span_or_holds_no_pause_gives_none(
        monkeypatch):
    everything = STALLS + SETUP
    # the parent: every span of the step loop, and no pause span
    ring = Ring()
    _quiet(ring, 20)
    ring.step(wait=0.3)
    run = ring.run()
    assert {m: getattr(pauses, m)(run) for m in everything} == \
        dict.fromkeys(everything)
    # a dropped span: `program_spans` hands on nothing
    monkeypatch.setattr(span_reader, "_dropped", lambda: 1.0)
    run = {"kind": "serve", "window": (H0, H0 + 1.0)}
    assert {m: getattr(pauses, m)(run) for m in everything} == \
        dict.fromkeys(everything)
    assert run["_program_spans"] is None


def test_each_new_metric_has_its_file_its_reader_and_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    every = [w["name"] for w in bench["workloads"]]
    for name in STALLS + SETUP:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               f"{name}.json")) as f:
            spec = json.load(f)
        mod, fn = spec["reader"].split(":")
        assert mod == "pauses" and callable(getattr(pauses, fn))
        assert spec["catches"] and spec["source"] == "program_span"
        entry = entries[name]
        assert entry["source"] == "program_span" \
            and entry["better"] == "lower"
        for key in ("unit", "layer", "moves"):
            assert entry[key] == spec[key]
        if spec["unit"] == "%":
            assert spec["max"] == 100
        want = e2e["out_tok_s"]["workloads"] if name in STALLS else every
        assert entry["moves"] == ("out_tok_s" if name in STALLS
                                  else "setup_s")
        assert entry["workloads"] == want
    assert all(json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", f"{n}.json")))["args"]
        == {"over_ms": 50} for n in STALLS[:4])


@pytest.mark.parametrize("cell, want", [
    ("gpt_1p3b_serve.decode_closed64", STALLS + SETUP),
    ("gpt_350m_train.b16s1024", SETUP)])
def test_a_rehearsal_lists_the_new_names(cell, want):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearse.py"),
         "--workload", cell, "--trace", "1"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True
    assert set(want) <= set(line["would_report"])
    if want is SETUP:
        assert not set(STALLS) & set(line["would_report"])

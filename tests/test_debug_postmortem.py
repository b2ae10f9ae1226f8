"""Flight recorder, stall watchdog, and postmortem debug bundles
(ISSUE 5): ring bounds + disable, watchdog correctness (zero false
positives on slow-but-progressing loops, fault-injected hangs detected
within the deadline), CRC'd bundle round-trips, the `debug_dump` verb
on both network tiers, the wedged-engine e2e with a trace-id-keyed
flight timeline, and the multi-rank bundle aggregator."""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.observability import debug as obs_debug
from paddle_tpu.observability import flight as obs_flight
from paddle_tpu.observability import watchdog as obs_watchdog
from paddle_tpu.observability.debug import (BundleError, list_bundles,
                                            load_bundle, write_bundle)
from paddle_tpu.observability.flight import FlightRecorder
from paddle_tpu.observability.watchdog import Watchdog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_ring_bounded_per_tier_and_counts_drops():
    rec = FlightRecorder(max_events=4, enabled=True)
    for i in range(10):
        rec.record("chatty", "tick", i=i)
    rec.record("sparse", "snapshot", seq=1)
    chatty = rec.events("chatty")
    # ring kept only the newest 4; the sparse tier was not evicted
    assert [e.attrs["i"] for e in chatty] == [6, 7, 8, 9]
    assert len(rec.events("sparse")) == 1
    snap = rec.snapshot()
    assert len(snap["tiers"]["chatty"]) == 4
    assert snap["tiers"]["sparse"][0]["kind"] == "snapshot"
    # events are monotonic-ordered in the merged view
    all_ev = rec.events()
    assert all(a.ts <= b.ts for a, b in zip(all_ev, all_ev[1:]))


def test_flight_disabled_records_nothing():
    rec = FlightRecorder(max_events=8, enabled=False)
    assert rec.record("t", "k") is None
    assert rec.events() == [] and rec.snapshot()["tiers"] == {}
    rec.set_enabled(True)
    assert rec.record("t", "k") is not None
    assert len(rec.events("t")) == 1


def test_flight_timeline_keyed_by_trace_id_and_json_safe():
    rec = FlightRecorder(max_events=64, enabled=True)
    rec.record("serving", "submit", trace_id="aa11", request=1)
    rec.record("rpc", "server_request", trace_id="aa11", op="generate")
    rec.record("serving", "submit", trace_id="bb22", request=2)
    rec.record("serving", "weird", trace_id="aa11",
               arr=np.arange(3), scalar=np.int64(7), obj=object())
    tl = rec.timeline("aa11")
    assert [e.tier for e in tl] == ["serving", "rpc", "serving"]
    # snapshot is strict-JSON-safe even with numpy/object attrs
    text = json.dumps(rec.snapshot())
    parsed = json.loads(text)
    weird = parsed["tiers"]["serving"][-1]["attrs"]
    assert weird["arr"] == [0, 1, 2] and weird["scalar"] == 7
    assert isinstance(weird["obj"], str)


# ---------------------------------------------------------------------------
# watchdog correctness (satellite: zero false positives on slow
# progress; hangs fire within the deadline)
# ---------------------------------------------------------------------------

def test_watchdog_slow_but_progressing_never_fires():
    """A loop that advances its counter on every poll — however slowly
    — must produce ZERO stall reports."""
    wd = Watchdog(debug_dir=None)
    v = [0]
    wd.watch("slow", probe=lambda: v[0], deadline=0.05)
    for _ in range(10):
        time.sleep(0.02)        # slower than... nothing: it advances
        v[0] += 1
        assert wd.check_once() == []
    assert wd.stalled() == []
    # even a probe slower than the deadline is fine as long as it
    # advances between polls spaced past the deadline
    wd2 = Watchdog(debug_dir=None)
    wd2.watch("slower", probe=lambda: v[0], deadline=0.01)
    for _ in range(4):
        v[0] += 1
        assert wd2.check_once() == []
        time.sleep(0.03)        # poll gap > deadline, but progress each
        v[0] += 1
    assert wd2.check_once() == [] and wd2.stalled() == []


def test_watchdog_idle_tier_never_fires():
    wd = Watchdog(debug_dir=None)
    wd.watch("idle", probe=lambda: 42, deadline=0.01,
             idle=lambda: True)
    wd.check_once()
    time.sleep(0.05)
    assert wd.check_once() == [] and wd.stalled() == []


def test_watchdog_fires_once_per_episode_and_recovers(tmp_path):
    fired = []
    wd = Watchdog(debug_dir=str(tmp_path))
    v = [1]
    wd.watch("tok", probe=lambda: v[0], deadline=0.05,
             on_stall=lambda name, age, path: fired.append(
                 (name, age, path)))
    wd.check_once()             # baseline
    time.sleep(0.08)
    assert wd.check_once() == ["tok"]          # fired
    assert wd.check_once() == []               # once per episode
    assert wd.stalled() == ["tok"]
    (name, age, path), = fired
    assert name == "tok" and age > 0.05
    # the fire wrote a complete, parseable bundle
    b = load_bundle(path)
    assert b["manifest"]["reason"] == "watchdog:tok"
    assert "paddle_tpu_watchdog_stalls_total" in b["files"]["metrics.prom"]
    # progress clears the episode; a later hang fires again
    v[0] += 1
    assert wd.check_once() == [] and wd.stalled() == []
    time.sleep(0.08)
    assert wd.check_once() == ["tok"]
    wd.unwatch("tok")
    assert wd.tokens() == []


def test_watchdog_dead_probe_unregisters():
    wd = Watchdog(debug_dir=None)
    wd.watch("gone", probe=lambda: None, deadline=0.01)
    wd.check_once()
    assert wd.tokens() == []


def test_watchdog_healthy_predicate_and_heartbeats(tmp_path):
    from paddle_tpu.distributed.elastic import HeartbeatWriter
    wd = Watchdog(debug_dir=None)
    hb = HeartbeatWriter(str(tmp_path), rank=0, interval=0.05).start()
    try:
        wd.watch_heartbeats(str(tmp_path), timeout=0.5, expected=1,
                            deadline=0.05)
        wd.check_once()
        time.sleep(0.1)
        assert wd.check_once() == []           # beating = healthy
    finally:
        hb.stop()
    time.sleep(0.7)                            # beats go stale
    fired = wd.check_once()
    if not fired:                              # unhealth just started
        time.sleep(0.07)
        fired = wd.check_once()
    assert fired == ["elastic.heartbeats"]


# ---------------------------------------------------------------------------
# bundles
# ---------------------------------------------------------------------------

def test_bundle_write_load_roundtrip_and_crc(tmp_path):
    obs_flight.record("test", "bundle_marker", answer=42)
    path = write_bundle(str(tmp_path), reason="unit")
    assert os.path.basename(path).startswith("bundle_")
    b = load_bundle(path)
    assert b["manifest"]["reason"] == "unit"
    assert set(b["files"]) == {"metrics.prom", "metrics.json",
                               "trace.json", "flight.json", "env.json",
                               "requests.json"}
    # sections are the real surfaces
    assert "# TYPE" in b["files"]["metrics.prom"]
    assert "traceEvents" in b["files"]["trace.json"]
    tiers = b["files"]["flight.json"]["tiers"]
    assert any(e["kind"] == "bundle_marker"
               for e in tiers.get("test", []))
    assert b["files"]["env.json"]["versions"]["python"]
    # corrupting any file fails the CRC verification
    with open(os.path.join(path, "flight.json"), "ab") as f:
        f.write(b"x")
    with pytest.raises(BundleError):
        load_bundle(path)
    assert list_bundles(str(tmp_path))[0]["valid"] is False


def test_bundle_commit_is_atomic(tmp_path):
    # a half-written temp dir is never listed as a bundle
    os.makedirs(tmp_path / ".tmp_bundle_h_1_2_3")
    (tmp_path / ".tmp_bundle_h_1_2_3" / "metrics.prom").write_text("x")
    assert list_bundles(str(tmp_path)) == []


def test_aggregator_lists_and_merges_bundles(tmp_path):
    """Multi-rank story (launch.py --debug_dir): several processes each
    leave a bundle; the offline aggregator lists them and merges their
    metrics with the plain metrics_*.json dumps."""
    from paddle_tpu.observability.debug import aggregate_with_bundles
    write_bundle(str(tmp_path), reason="rank0")
    write_bundle(str(tmp_path), reason="rank0-later")
    # ANOTHER rank's exit-time metrics dump sits next to the bundles
    from paddle_tpu.observability.registry import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter("paddle_tpu_t_agg_total", "t").inc(5)
    other = reg.to_dict()
    other["pid"] = 99999
    with open(tmp_path / "metrics_h_99999.json", "w") as f:
        json.dump(other, f)
    agg = aggregate_with_bundles(str(tmp_path))
    # both bundles came from THIS process: overlapping snapshots, so
    # only the newest contributes metrics (no double counting across
    # bundles OR against a same-process metrics dump) — while the
    # other rank's dump still adds, and the listing shows everything
    assert agg["aggregated_from"] == 2
    assert [b["reason"] for b in agg["bundles"]] == ["rank0",
                                                     "rank0-later"]
    assert all(b["valid"] for b in agg["bundles"])
    by_name = {m["name"]: m for m in agg["metrics"]}
    assert by_name["paddle_tpu_t_agg_total"]["samples"][0]["value"] == 5
    # the CLI module prints the same shape
    res = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.observability.registry",
         str(tmp_path)],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 PYTHONPATH=REPO + os.pathsep
                 + os.environ.get("PYTHONPATH", "")))
    assert res.returncode == 0, res.stderr
    out = json.loads(res.stdout)
    assert len(out["bundles"]) == 2


def test_launch_parser_accepts_debug_dir():
    from paddle_tpu.distributed.launch import _parse
    args = _parse(["--debug_dir", "/tmp/x", "--metrics_dir", "/tmp/y",
                   "train.py"])
    assert args.debug_dir == "/tmp/x"


def test_unhandled_exception_writes_bundle(tmp_path):
    prog = tmp_path / "boom.py"
    prog.write_text(
        "from paddle_tpu import observability as obs\n"
        "obs.flight.record('app', 'about_to_die')\n"
        "raise RuntimeError('boom')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_DEBUG_DIR=str(tmp_path / "d"),
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    res = subprocess.run([sys.executable, str(prog)], env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode != 0 and "boom" in res.stderr
    bundles = list_bundles(str(tmp_path / "d"))
    assert len(bundles) == 1 and bundles[0]["valid"]
    assert bundles[0]["reason"] == "excepthook:RuntimeError"
    b = load_bundle(bundles[0]["path"])
    tiers = b["files"]["flight.json"]["tiers"]
    assert any(e["kind"] == "about_to_die" for e in tiers["app"])


def test_sigterm_dump_includes_trace_flight_and_bundle(tmp_path):
    """Satellite: the PR-3 SIGTERM hook now dumps the trace ring and
    flight events next to the metrics JSON, and a full bundle when
    PADDLE_TPU_DEBUG_DIR is set — exit code stays 143-equivalent."""
    prog = tmp_path / "victim.py"
    prog.write_text(
        "import time\n"
        "from paddle_tpu import observability as obs\n"
        "obs.counter('paddle_tpu_sigterm2_units_total', 'u').inc(2)\n"
        "with obs.span('victim.work'):\n"
        "    obs.flight.record('app', 'working')\n"
        "print('READY', flush=True)\n"
        "time.sleep(120)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_METRICS_DIR=str(tmp_path / "m"),
               PADDLE_TPU_DEBUG_DIR=str(tmp_path / "d"),
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.Popen([sys.executable, str(prog)], env=env,
                            stdout=subprocess.PIPE, text=True)
    try:
        assert proc.stdout.readline().strip() == "READY"
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == -signal.SIGTERM
    mdir = tmp_path / "m"
    files = sorted(os.listdir(mdir))
    assert any(f.startswith("metrics_") for f in files)
    trace = [f for f in files if f.startswith("trace_")]
    flight = [f for f in files if f.startswith("flight_")]
    assert trace and flight
    tr = json.load(open(mdir / trace[0]))
    assert any(e["name"] == "victim.work" for e in tr["traceEvents"])
    fl = json.load(open(mdir / flight[0]))
    assert any(e["kind"] == "working" for e in fl["tiers"]["app"])
    bundles = list_bundles(str(tmp_path / "d"))
    assert len(bundles) == 1 and bundles[0]["valid"]
    assert bundles[0]["reason"] == "sigterm"


# ---------------------------------------------------------------------------
# serving tier: debug_dump verb + the wedged-engine e2e
# ---------------------------------------------------------------------------

@pytest.fixture()
def engine():
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving import Engine, GPTDecodeModel
    model = GPTDecodeModel(GPTConfig.tiny(num_layers=1), seed=0)
    return Engine(model, num_slots=2, num_pages=16, page_size=4,
                  max_seq_len=32)


def test_serving_debug_dump_verb_healthy(engine, tmp_path,
                                         monkeypatch):
    """Acceptance: `debug_dump` on a HEALTHY server returns a bundle
    equivalent to the on-disk one (same sections, with the engine's
    request table and its flight timeline). The write lands in the
    SERVER's PADDLE_TPU_DEBUG_DIR — never a wire-chosen path."""
    from paddle_tpu.serving import ServingClient, ServingServer
    monkeypatch.setenv("PADDLE_TPU_DEBUG_DIR", str(tmp_path))
    # a live shared secret must never ride a bundle or the wire reply
    monkeypatch.setenv("PADDLE_PS_SECRET", "hunter2-do-not-leak")
    with ServingServer(engine, "127.0.0.1:0") as srv:
        cli = ServingClient(srv.endpoint)
        try:
            rep = cli.generate([1, 2, 3], max_new_tokens=3, timeout=60)
            assert rep["status"] == "done"
            bundle = cli.debug_dump()
        finally:
            cli.close()
    assert bundle["reason"] == "debug_dump"
    # in-memory sections == what collect() defines
    for key in ("metrics_text", "metrics", "trace", "flight", "env",
                "requests"):
        assert key in bundle, key
    prov = bundle["requests"][f"serving.engine.{engine.engine_id}"]
    assert prov["inflight"] == []          # healthy: nothing stuck
    assert any(r["status"] == "done" for r in prov["recent"])
    # secret redaction: the env section names the var but not its value
    assert bundle["env"]["env"]["PADDLE_PS_SECRET"] == "<redacted>"
    assert "hunter2-do-not-leak" not in json.dumps(bundle["env"])
    # and the same content committed to disk, CRC-verified
    disk = load_bundle(bundle["path"])
    assert disk["manifest"]["reason"] == "debug_dump"
    assert disk["files"]["metrics.prom"] == bundle["metrics_text"]
    assert disk["files"]["requests.json"] == \
        json.loads(json.dumps(bundle["requests"]))


def test_prefill_only_traffic_is_progress_not_a_stall(monkeypatch):
    """Regression: a healthy stream of requests that all finish at
    prefill (max_new_tokens=1) never runs a decode step — decode-step
    count alone would look stalled while the queue stays non-empty, but
    finishing requests IS progress and the watchdog must stay quiet."""
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.observability.watchdog import WATCHDOG
    from paddle_tpu.serving import Engine, GPTDecodeModel

    monkeypatch.setenv("PADDLE_TPU_WATCHDOG_DEADLINE", "0.2")
    model = GPTDecodeModel(GPTConfig.tiny(num_layers=1), seed=0)
    eng = Engine(model, num_slots=2, num_pages=16, page_size=4,
                 max_seq_len=32)
    token = f"serving.engine.{eng.engine_id}"
    try:
        eng.start()
        t0 = time.monotonic()
        while time.monotonic() - t0 < 0.8:   # >> deadline of healthy
            r = eng.submit([1, 2, 3], max_new_tokens=1)
            assert r.wait(timeout=60)
            assert r.status == "done", r.status
            assert token not in WATCHDOG.check_once()
        assert eng.stats()["steps"] == 0     # truly prefill-only
        assert token not in WATCHDOG.stalled()
    finally:
        eng.stop()


def test_wedged_engine_detected_with_trace_keyed_timeline(tmp_path,
                                                          monkeypatch):
    """Acceptance e2e: a wedged serving engine is detected by the
    watchdog within its deadline, and the bundle contains metrics, the
    trace ring, and the stuck request's flight timeline keyed by its
    trace id."""
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.observability.watchdog import WATCHDOG
    from paddle_tpu.serving import Engine, GPTDecodeModel

    monkeypatch.setenv("PADDLE_TPU_WATCHDOG_DEADLINE", "0.3")

    class Wedged(GPTDecodeModel):
        """decode blocks until released — a wedged jitted step, the
        serving tier's watchdog target."""
        release = threading.Event()
        entered = threading.Event()

        def decode(self, *a, **k):
            # block OUTSIDE the trace (fixture engines compile eagerly
            # enough); a hung host callback models a wedged device step
            self.entered.set()
            self.release.wait()
            return super().decode(*a, **k)

    model = Wedged(GPTConfig.tiny(num_layers=1), seed=0)
    eng = Engine(model, num_slots=2, num_pages=16, page_size=4,
                 max_seq_len=32)
    token = f"serving.engine.{eng.engine_id}"
    assert token in WATCHDOG.tokens()
    try:
        eng.start()
        req = eng.submit([5, 6, 7], max_new_tokens=8)
        assert req.trace_id           # minted even without a wire hop
        # wait until the prefill is DISPATCHED and the step's decode
        # entered: the engine thread is then wedged inside the decode
        # step, in front of the read of the prefill's first token
        assert model.entered.wait(60), "the decode was never dispatched"
        assert not req.generated
        assert eng.scheduler.active_requests(), "request not running"

        # drive the watchdog the way its poll thread would; detection
        # must happen within ~deadline + one poll interval
        WATCHDOG.debug_dir = str(tmp_path)
        try:
            t0 = time.monotonic()
            fired = []
            while not fired and time.monotonic() - t0 < 10:
                fired = [t for t in WATCHDOG.check_once()
                         if t == token]
                time.sleep(0.05)
        finally:
            WATCHDOG.debug_dir = None
        assert fired == [token], "watchdog missed the wedged engine"
        detect_s = time.monotonic() - t0
        assert detect_s < 5, f"detection took {detect_s}s"

        bundles = [r for r in list_bundles(str(tmp_path))
                   if r["reason"] == f"watchdog:{token}"]
        assert bundles and bundles[0]["valid"]
        b = load_bundle(bundles[0]["path"])
        # metrics: the stall is on the board
        assert "paddle_tpu_watchdog_stalls_total" \
            in b["files"]["metrics.prom"]
        # trace ring present (chrome trace_event doc)
        assert isinstance(b["files"]["trace.json"]["traceEvents"], list)
        # the stuck request's timeline, keyed by ITS trace id
        tiers = b["files"]["flight.json"]["tiers"]
        mine = [e for evs in tiers.values() for e in evs
                if e.get("trace_id") == req.trace_id]
        kinds = {e["kind"] for e in mine}
        assert {"submit", "admit", "prefill"} <= kinds, kinds
        # and the in-flight table names it as running in a slot
        prov = b["files"]["requests.json"][token]
        stuck = [r for r in prov["inflight"] if r["id"] == req.id]
        assert stuck and stuck[0]["status"] == "running"
        assert stuck[0]["trace_id"] == req.trace_id
    finally:
        model.release.set()
        eng.stop()
    # recovery clears the episode
    eng.run_until_idle()
    assert token not in WATCHDOG.check_once()
    assert token not in WATCHDOG.stalled()


# ---------------------------------------------------------------------------
# PS tier: fault-injected hang + debug_dump verb
# ---------------------------------------------------------------------------

def test_ps_fault_injected_hang_fires_and_bundle_parses(tmp_path,
                                                        monkeypatch):
    """Satellite: a fault-injected hang (fault_injection stall knob)
    must produce a complete, parseable bundle within the deadline —
    and the healthy path before it produces zero false positives."""
    from paddle_tpu.distributed.fleet.runtime.fault_injection import (
        FaultInjector, reset_injector)
    from paddle_tpu.distributed.fleet.runtime. \
        parameter_server_runtime import PSClient, PSServer
    from paddle_tpu.observability.watchdog import WATCHDOG

    monkeypatch.setenv("PADDLE_TPU_WATCHDOG_DEADLINE", "0.3")
    srv = PSServer("127.0.0.1:0")
    srv.serve_in_thread()
    token = srv._wd_name
    cl = PSClient([srv.endpoint])
    try:
        keys = np.array([1, 2], np.int64)
        cl.pull("emb", 4, keys)
        cl.push("emb", 4, keys, np.ones((2, 4), np.float32))
        # healthy traffic: no stall however often we poll
        for _ in range(3):
            assert token not in WATCHDOG.check_once()
        # inject the hang: the next dispatch wedges server-side
        reset_injector(FaultInjector(stall=4.0,
                                     stall_point="dispatch",
                                     side="server"))
        hung = threading.Thread(
            target=lambda: cl.pull("emb", 4, keys), daemon=True)
        hung.start()
        deadline = time.monotonic() + 10
        while srv._wd_inflight == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert srv._wd_inflight > 0, "stalled dispatch never arrived"
        WATCHDOG.debug_dir = str(tmp_path)
        try:
            t0 = time.monotonic()
            fired = []
            while not fired and time.monotonic() - t0 < 8:
                fired = [t for t in WATCHDOG.check_once()
                         if t == token]
                time.sleep(0.05)
        finally:
            WATCHDOG.debug_dir = None
        assert fired == [token], "watchdog missed the hung PS dispatch"
        bundles = [r for r in list_bundles(str(tmp_path))
                   if r["reason"] == f"watchdog:{token}"]
        assert bundles and bundles[0]["valid"]
        b = load_bundle(bundles[0]["path"])
        tiers = b["files"]["flight.json"]["tiers"]
        # the rings hold the PS story: pushes/pulls + the stall event
        assert any(e["kind"] == "push" for e in tiers.get("ps", []))
        assert any(e["kind"] == "stall"
                   and e["attrs"]["token"] == token
                   for e in tiers.get("watchdog", []))
        hung.join(timeout=30)
    finally:
        reset_injector(FaultInjector())
        cl.close()
        srv.shutdown()
        srv.server_close()


def test_ps_debug_dump_verb(tmp_path, monkeypatch):
    from paddle_tpu.distributed.fleet.runtime. \
        parameter_server_runtime import PSClient, PSServer
    monkeypatch.setenv("PADDLE_TPU_DEBUG_DIR", str(tmp_path))
    srv = PSServer("127.0.0.1:0")
    srv.serve_in_thread()
    cl = PSClient([srv.endpoint])
    try:
        keys = np.array([3], np.int64)
        cl.push("emb", 4, keys, np.ones((1, 4), np.float32))
        rep = cl.debug_dump(shard=0)
        assert rep["reason"] == "debug_dump"
        assert "paddle_tpu_rpc_server_requests_total" \
            in rep["metrics_text"]
        assert any(e["kind"] == "push"
                   for e in rep["flight"]["tiers"].get("ps", []))
        disk = load_bundle(rep["path"])
        assert disk["manifest"]["reason"] == "debug_dump"
    finally:
        cl.close()
        srv.shutdown()
        srv.server_close()


# ---------------------------------------------------------------------------
# checkpoint async-writer instrumentation (satellite)
# ---------------------------------------------------------------------------

def test_ckpt_writer_gauges_and_flight_transitions(tmp_path):
    from paddle_tpu.checkpoint import CheckpointStore
    from paddle_tpu.observability import REGISTRY
    depth = REGISTRY.get("paddle_tpu_ckpt_writer_queue_depth")
    pending = REGISTRY.get("paddle_tpu_ckpt_writer_pending_bytes")
    inflight = REGISTRY.get("paddle_tpu_ckpt_inflight_save_seconds")
    st = CheckpointStore(str(tmp_path))
    state = {"w": np.arange(1024, dtype=np.float32)}
    obs_flight.RECORDER.clear()
    step = st.save_async(state)
    st.wait()
    # drained: the live gauges read zero again
    assert depth.value == 0 and pending.value == 0
    assert inflight.value == 0
    # queue transitions hit the flight ring: enqueue -> write_start ->
    # write_done, with the payload bytes accounted
    kinds = [e.kind for e in obs_flight.RECORDER.events("ckpt")]
    for k in ("enqueue", "write_start", "write_done",
              "manifest_commit"):
        assert k in kinds, (k, kinds)
    enq = [e for e in obs_flight.RECORDER.events("ckpt")
           if e.kind == "enqueue"][0]
    assert enq.attrs["bytes"] == 4096 and enq.attrs["step"] == step
    got, _meta = st.restore()
    np.testing.assert_array_equal(got["w"], state["w"])


# ---------------------------------------------------------------------------
# static ratchet: the new names are REQUIRED
# ---------------------------------------------------------------------------

def test_required_metric_ratchet_covers_watchdog_and_flight(tmp_path):
    """Deleting the watchdog/flight/ckpt-writer registrations must fail
    scripts/check_metric_names.py (same ratchet as the ckpt names)."""
    from scripts.check_metric_names import REQUIRED_METRICS
    for name in ("paddle_tpu_watchdog_stalls_total",
                 "paddle_tpu_watchdog_stalled",
                 "paddle_tpu_flight_events_total",
                 "paddle_tpu_flight_dropped_total",
                 "paddle_tpu_ckpt_writer_queue_depth",
                 "paddle_tpu_ckpt_inflight_save_seconds"):
        assert name in REQUIRED_METRICS, name

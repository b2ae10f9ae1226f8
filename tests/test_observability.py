"""Unified runtime telemetry: registry exposition, tracing, cross-tier
trace ids, the serving/PS `metrics` verbs, and the metric-name static
check (scripts/check_metric_names.py)."""
import json
import os
import re
import subprocess
import sys
import threading

import numpy as np
import pytest

from paddle_tpu.observability import registry as obs_registry
from paddle_tpu.observability import tracing as obs_tracing
from paddle_tpu.observability.registry import (MetricError,
                                               MetricsRegistry,
                                               aggregate_dir,
                                               aggregate_dumps)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# registry basics
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_values():
    reg = MetricsRegistry()
    c = reg.counter("paddle_tpu_t_reqs_total", "requests", ["op"])
    c.labels(op="a").inc()
    c.labels(op="a").inc(4)
    c.labels(op="b").inc()
    assert c.labels(op="a").value == 5 and c.labels(op="b").value == 1
    g = reg.gauge("paddle_tpu_t_depth", "depth")
    g.set(7)
    g.inc(2)
    g.dec()
    assert g.value == 8
    h = reg.histogram("paddle_tpu_t_lat_seconds", "lat",
                      buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    cum, s, n = h.snapshot()
    assert cum == [1, 2, 3] and n == 3 and abs(s - 5.55) < 1e-9
    with pytest.raises(MetricError):
        c.labels(op="a").inc(-1)      # counters only go up
    with pytest.raises(MetricError):
        c.labels(wrong="a")           # label names must match


def test_registration_is_idempotent_but_conflicts_raise():
    reg = MetricsRegistry()
    a = reg.counter("paddle_tpu_t_total", "x", ["k"])
    assert reg.counter("paddle_tpu_t_total", "x", ["k"]) is a
    with pytest.raises(MetricError):
        reg.gauge("paddle_tpu_t_total", "x", ["k"])    # kind conflict
    with pytest.raises(MetricError):
        reg.counter("paddle_tpu_t_total", "x", ["j"])  # label conflict
    with pytest.raises(MetricError):
        reg.counter("bad_name_total")                  # prefix rule
    with pytest.raises(MetricError):
        reg.counter("paddle_tpu_CamelCase")            # snake_case rule


def test_prometheus_text_parses():
    """Exposition format: HELP/TYPE headers, name{label="v"} value
    lines, and the _bucket/_sum/_count histogram triplet with
    cumulative le buckets ending at +Inf == _count."""
    reg = MetricsRegistry()
    reg.counter("paddle_tpu_t_reqs_total", "reqs",
                ["op"]).labels(op='we"ird\n').inc(3)
    h = reg.histogram("paddle_tpu_t_step_seconds", "steps",
                      buckets=(0.01, 0.1))
    h.observe(0.005)
    h.observe(0.05)
    text = reg.prometheus_text()
    lines = text.strip().splitlines()
    sample_re = re.compile(
        r'^([a-z_][a-z0-9_]*)(\{[^}]*\})? (-?[0-9.e+-]+|\+Inf|NaN)$')
    names = set()
    for ln in lines:
        if ln.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) paddle_tpu_[a-z0-9_]+", ln)
            continue
        m = sample_re.match(ln)
        assert m, f"unparseable exposition line: {ln!r}"
        names.add(m.group(1))
        float(m.group(3))  # every value is a number
    assert {"paddle_tpu_t_reqs_total", "paddle_tpu_t_step_seconds_bucket",
            "paddle_tpu_t_step_seconds_sum",
            "paddle_tpu_t_step_seconds_count"} <= names
    # label escaping survived
    assert 'op="we\\"ird\\n"' in text
    # cumulative buckets: 0.01 -> 1, 0.1 -> 2, +Inf -> 2 == count
    assert 'le="0.01"} 1' in text and 'le="0.1"} 2' in text
    assert 'le="+Inf"} 2' in text
    assert "paddle_tpu_t_step_seconds_count 2" in text


def test_json_dump_round_trips_and_aggregates(tmp_path):
    def make(n):
        reg = MetricsRegistry()
        reg.counter("paddle_tpu_t_total", "t", ["op"]).labels(
            op="x").inc(n)
        reg.gauge("paddle_tpu_t_gauge", "g").set(n)
        h = reg.histogram("paddle_tpu_t_seconds", "h", buckets=(1.0,))
        h.observe(0.5)
        return reg

    r1, r2 = make(2), make(5)
    # round trip through the on-disk JSON
    p1 = r1.dump_to_file(str(tmp_path / "metrics_h_1.json"))
    p2 = r2.dump_to_file(str(tmp_path / "metrics_h_2.json"))
    d1 = json.load(open(p1))
    assert d1["metrics"] == r1.to_dict()["metrics"]
    # aggregation: counters/histograms sum, gauges keep the newest
    agg = aggregate_dir(str(tmp_path))
    assert agg["aggregated_from"] == 2
    by_name = {m["name"]: m for m in agg["metrics"]}
    assert by_name["paddle_tpu_t_total"]["samples"][0]["value"] == 7
    assert by_name["paddle_tpu_t_seconds"]["samples"][0]["count"] == 2
    assert by_name["paddle_tpu_t_seconds"]["samples"][0]["sum"] == 1.0
    assert by_name["paddle_tpu_t_gauge"]["samples"][0]["value"] == 5
    # the aggregate of one dump is that dump
    one = aggregate_dumps([r1.to_dict()])
    assert {m["name"] for m in one["metrics"]} == set(
        m["name"] for m in d1["metrics"])


def test_sigterm_writes_metrics_dump(tmp_path):
    """launch.py stops PS servers with SIGTERM, which skips atexit —
    the observability import installs a SIGTERM hook (over the default
    disposition only) that dumps the registry first and preserves the
    143 exit."""
    import signal
    import time
    prog = tmp_path / "victim.py"
    prog.write_text(
        "import time\n"
        "from paddle_tpu import observability as obs\n"
        "obs.counter('paddle_tpu_sigterm_units_total', 'u').inc(3)\n"
        "print('READY', flush=True)\n"
        "time.sleep(120)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_METRICS_DIR=str(tmp_path / "m"),
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
    assert rc == -signal.SIGTERM  # default disposition preserved
    deadline = time.time() + 10
    dumps = []
    while not dumps and time.time() < deadline:
        dumps = [f for f in os.listdir(tmp_path / "m")
                 if f.endswith(".json")]
    assert dumps, "no metrics dump written on SIGTERM"
    agg = aggregate_dir(str(tmp_path / "m"))
    by_name = {m["name"]: m for m in agg["metrics"]}
    assert by_name["paddle_tpu_sigterm_units_total"][
        "samples"][0]["value"] == 3


def test_per_instance_series_removed_on_gc():
    """A dead engine's labeled series (incl. weakref gauges) leave the
    exposition instead of accumulating forever."""
    import gc
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.observability import REGISTRY
    from paddle_tpu.serving import Engine, GPTDecodeModel
    model = GPTDecodeModel(GPTConfig.tiny(num_layers=1), seed=0)
    eng = Engine(model, num_slots=2, num_pages=8, page_size=4)
    eid = eng.engine_id
    reqs = REGISTRY.get("paddle_tpu_serving_requests_total")
    gauge = REGISTRY.get("paddle_tpu_serving_queue_depth")
    assert any(v == (eid,) for v, _ in reqs._series())
    assert any(v == (eid,) for v, _ in gauge._series())
    del eng
    gc.collect()
    assert not any(v == (eid,) for v, _ in reqs._series())
    assert not any(v == (eid,) for v, _ in gauge._series())
    admitted = REGISTRY.get("paddle_tpu_serving_admitted_total")
    assert not any(v == (eid,) for v, _ in admitted._series())


def test_always_series_survive_kill_switch():
    """The registry-backed legacy stats (PagePool/Scheduler counters)
    keep counting with telemetry disabled — the kill switch gates
    exposition-only series, not functional surfaces."""
    from paddle_tpu.observability import set_enabled
    from paddle_tpu.serving import PagePool
    pool = PagePool(4, 16)
    set_enabled(False)
    try:
        pool.alloc(2)
        assert pool.alloc(8) is None
        assert pool.alloc_count == 2 and pool.alloc_failures == 1
        assert pool.used_pages == 2  # consistent with the counters
    finally:
        set_enabled(True)


def test_counter_concurrency_loses_no_increments():
    """8 threads hammering one labeled child and the whole family."""
    reg = MetricsRegistry()
    c = reg.counter("paddle_tpu_t_hammer_total", "t", ["op"])
    h = reg.histogram("paddle_tpu_t_hammer_seconds", "t",
                      buckets=(0.5,))
    N, T = 10000, 8
    barrier = threading.Barrier(T)

    def work():
        barrier.wait()
        child = c.labels(op="x")
        for _ in range(N):
            child.inc()
            h.observe(0.1)

    threads = [threading.Thread(target=work) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert c.labels(op="x").value == N * T
    assert h.count == N * T


def test_disable_is_a_noop_switch():
    reg = MetricsRegistry()
    c = reg.counter("paddle_tpu_t_total", "t")
    c.inc()
    reg.set_enabled(False)
    c.inc(100)
    assert c.value == 1
    reg.set_enabled(True)
    c.inc()
    assert c.value == 2


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def test_span_nesting_and_chrome_export(tmp_path):
    tr = obs_tracing.Tracer()
    with tr.span("outer", tier="t") as o:
        assert tr.current_trace_id() == o.trace_id
        with tr.span("inner") as i:
            pass
    assert i.trace_id == o.trace_id and i.parent_id == o.span_id
    assert tr.current_trace_id() is None
    path = str(tmp_path / "trace.json")
    doc = tr.export_chrome_trace(path)
    disk = json.load(open(path))
    assert disk == doc
    evs = {e["name"]: e for e in doc["traceEvents"]}
    assert evs["outer"]["ph"] == "X" and evs["outer"]["dur"] >= 0
    assert evs["outer"]["args"]["tier"] == "t"
    assert evs["inner"]["args"]["trace_id"] == \
        evs["outer"]["args"]["trace_id"]


def test_span_trace_id_reroot_and_disabled_propagation():
    tr = obs_tracing.Tracer()
    with tr.span("rooted", trace_id="cafe01"):
        assert tr.current_trace_id() == "cafe01"
    tr.enabled = False
    with tr.span("quiet", trace_id="beef02"):
        # ids still propagate for cross-process correlation...
        assert tr.current_trace_id() == "beef02"
    # ...but nothing was recorded
    assert all(s.name != "quiet" for s in tr.spans())


# ---------------------------------------------------------------------------
# e2e: one served generate request -> one trace id across tiers + a
# metrics verb whose counters moved + unchanged stats surfaces
# ---------------------------------------------------------------------------

ENGINE_STATS_KEYS = {
    "queue_depth", "active_slots", "num_slots", "admitted", "completed",
    "preemptions", "rejected", "pool", "steps", "tokens_generated",
    "tokens_per_sec", "latency_ms_p50", "latency_ms_p99",
    "completed_seen", "compiles",
    # PR-6 admission control: every PR-2 key above is unchanged; the
    # scheduler's new decision counters ride along
    "expired_in_queue", "shed", "quota_rejected",
    # PR-9 graceful drain: the router reads it from ping/stats
    "draining",
    # PR-12 online learning: published-version identity so loadgen can
    # slice SLO windows pre/post hot swap
    "model_version",
    # PR-14 perf plane: live efficiency surface — a fleet scrape
    # answers the MFU question without a profiler
    "tokens_per_s_per_chip", "mfu",
    # PR-19 shared-prefix KV reuse: cache stats block (None when the
    # cache is disabled, which is the default)
    "prefix_cache",
    # ISSUE 31 the step loop runs a decode ahead of its reads: how often,
    # and the tokens of requests that ended under a decode in flight
    "decodes_ahead", "tokens_discarded"}
POOL_STATS_KEYS = {
    "num_pages", "page_size", "free_pages", "used_pages", "occupancy",
    "alloc_count", "free_count", "alloc_failures",
    # PR-19: pages referenced by >1 holder (prefix sharing)
    "shared_pages"}


@pytest.fixture(scope="module")
def served():
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving import (Engine, GPTDecodeModel,
                                    ServingServer)
    cfg = GPTConfig.tiny(num_layers=2)
    model = GPTDecodeModel(cfg, seed=0)
    engine = Engine(model, num_slots=4, num_pages=32, page_size=8,
                    max_seq_len=64)
    with ServingServer(engine, "127.0.0.1:0") as srv:
        yield engine, srv


def _metric_value(text: str, name: str, **labels) -> float:
    """Sum of a metric's samples whose labels include `labels`."""
    total, seen = 0.0, False
    for ln in text.splitlines():
        if not ln.startswith(name):
            continue
        rest = ln[len(name):]
        if rest[:1] not in ("{", " "):
            continue
        if all(f'{k}="{v}"' in ln for k, v in labels.items()):
            total += float(ln.rsplit(" ", 1)[1])
            seen = True
    return total if seen else float("nan")


def test_e2e_trace_id_and_metrics_verb(served):
    from paddle_tpu.serving import ServingClient
    engine, srv = served
    obs_tracing.TRACER.clear()
    cli = ServingClient(srv.endpoint)
    try:
        before = cli.metrics()
        rep = cli.generate([3, 1, 4, 1], max_new_tokens=5, timeout=90)
        assert rep["status"] == "done" and len(rep["tokens"]) == 5
        after = cli.metrics()
    finally:
        cli.close()

    # (a) ONE trace id visible in both frontend and engine spans of the
    # Chrome export — the id traveled client -> wire -> handler ->
    # submit -> engine scheduler thread
    doc = obs_tracing.TRACER.export_chrome_trace()
    by_name = {}
    for ev in doc["traceEvents"]:
        by_name.setdefault(ev["name"], []).append(ev)
    fe = [e for e in by_name.get("frontend.generate", [])
          if e["args"].get("status") == "done"]
    assert fe, "no frontend.generate span recorded"
    tid = fe[-1]["args"]["trace_id"]
    eng_spans = [e for e in by_name.get("engine.prefill", [])
                 if e["args"]["trace_id"] == tid]
    assert eng_spans, "engine.prefill span does not share the " \
                      "frontend trace id"
    # the client-side rpc span carries it too (same process here)
    assert any(e["args"]["trace_id"] == tid
               for e in by_name.get("rpc.client", []))

    # (b) metrics verb: request count, decode-step histogram and
    # compile counters all moved across the generate
    eid = engine.engine_id
    assert _metric_value(after, "paddle_tpu_serving_requests_total",
                         engine=eid) \
        >= _metric_value(before, "paddle_tpu_serving_requests_total",
                         engine=eid) + 1
    assert _metric_value(
        after, "paddle_tpu_serving_decode_step_seconds_count",
        engine=eid) > 0
    assert _metric_value(after, "paddle_tpu_serving_compiles_total",
                         engine=eid) >= 2  # prefill + decode programs
    assert _metric_value(after, "paddle_tpu_rpc_server_requests_total",
                         op="generate") >= 1

    # (c) stats surfaces unchanged (PR-2 keys, exact)
    st = engine.stats()
    assert set(st) == ENGINE_STATS_KEYS
    assert set(st["pool"]) == POOL_STATS_KEYS
    assert st["completed"] >= 1 and st["tokens_generated"] >= 5
    assert isinstance(st["compiles"], dict) and st["compiles"]


def test_ps_client_stats_surface_unchanged_and_server_metrics_verb():
    from paddle_tpu.distributed.fleet.runtime.parameter_server_runtime \
        import PSClient, PSServer
    srv = PSServer("127.0.0.1:0")
    srv.serve_in_thread()
    try:
        cl = PSClient([srv.endpoint])
        keys = np.array([1, 2, 3], np.int64)
        cl.pull("emb", 4, keys)
        cl.push("emb", 4, keys, np.ones((3, 4), np.float32), lr=0.1)
        # PSClient.stats keys unchanged (PR-1 TransportStats surface)
        d = cl.stats.as_dict()
        assert set(d) == {"requests", "retries", "reconnects",
                          "timeouts", "corrupt_frames", "remote_errors",
                          "deadline_exceeded", "bytes_out", "bytes_in"}
        assert d["requests"] >= 2 and d["bytes_out"] > 0
        # metrics verb: Prometheus text with the rpc counters moved
        text = cl.metrics(shard=0)
        assert _metric_value(
            text, "paddle_tpu_rpc_server_requests_total", op="pull") >= 1
        assert _metric_value(
            text, "paddle_tpu_rpc_server_requests_total", op="push") >= 1
        cl.close()
    finally:
        srv.shutdown()
        srv.server_close()


def test_ps_snapshot_metrics_recorded(tmp_path):
    from paddle_tpu.distributed.fleet.runtime.parameter_server_runtime \
        import PSClient, PSServer
    from paddle_tpu.observability import REGISTRY
    snaps = REGISTRY.get("paddle_tpu_ps_snapshots_total")
    base_before = snaps.labels(kind="base").value
    srv = PSServer("127.0.0.1:0", snapshot_dir=str(tmp_path),
                   snapshot_every=1)
    srv.serve_in_thread()
    try:
        cl = PSClient([srv.endpoint])
        keys = np.array([7, 8], np.int64)
        cl.push("emb", 4, keys, np.ones((2, 4), np.float32))
        cl.push("emb", 4, keys, np.ones((2, 4), np.float32))
        assert snaps.labels(kind="base").value > base_before
        bytes_total = REGISTRY.get("paddle_tpu_ps_snapshot_bytes_total")
        assert bytes_total.labels(kind="base").value > 0
        secs = REGISTRY.get("paddle_tpu_ps_snapshot_write_seconds")
        assert secs.labels(kind="base").count \
            + secs.labels(kind="delta").count >= 2
        cl.close()
    finally:
        srv.shutdown()
        srv.server_close()


# ---------------------------------------------------------------------------
# executor + autobench telemetry
# ---------------------------------------------------------------------------

def test_executor_run_and_cache_counters(fresh_programs):
    from paddle_tpu.fluid import Executor, layers
    from paddle_tpu.observability import REGISTRY

    runs = REGISTRY.get("paddle_tpu_executor_runs_total")
    hits = REGISTRY.get("paddle_tpu_executor_cache_hits_total")
    compiles = REGISTRY.get("paddle_tpu_executor_compiles_total")
    run_secs = REGISTRY.get("paddle_tpu_executor_run_seconds")
    r0, h0, c0, s0 = (runs.value, hits.value, compiles.value,
                      run_secs.count)

    main, startup, scope = fresh_programs
    x = layers.data("x", [-1, 4], "float32")
    h = layers.fc(x, 4, act="relu")
    exe = Executor()
    exe.run(startup)
    feed = {"x": np.ones((2, 4), np.float32)}
    exe.run(main, feed=feed, fetch_list=[h])
    exe.run(main, feed=feed, fetch_list=[h])
    assert runs.value >= r0 + 3       # startup + 2 main runs
    assert compiles.value >= c0 + 1   # first main run traced+jitted
    assert hits.value >= h0 + 1       # second main run hit the cache
    assert run_secs.count >= s0 + 3   # every run timed


def test_autobench_records_structured_events(monkeypatch, caplog):
    import logging
    from paddle_tpu.observability import REGISTRY
    from paddle_tpu.ops import autobench

    monkeypatch.setattr(
        autobench, "_measure",
        lambda fn, make_args, reps: ({"fast": 0.001, "slow": 0.004}[fn],
                                     0.0))
    monkeypatch.setenv("PADDLE_TPU_AUTOBENCH_VERBOSE", "1")
    key = ("obs_test_shape", 128)
    autobench.clear()
    with caplog.at_level(logging.INFO, logger="paddle_tpu.autobench"):
        winner = autobench.prefer(key, {"slow": "slow", "fast": "fast"},
                                  lambda: ())
    assert winner == "fast"
    assert any("obs_test_shape" in r.message for r in caplog.records)
    wgauge = REGISTRY.get("paddle_tpu_autobench_winner")
    assert wgauge.labels(key=str(key), candidate="fast").value == 1.0
    assert wgauge.labels(key=str(key), candidate="slow").value == 0.0
    cand = REGISTRY.get("paddle_tpu_autobench_candidate_ms")
    assert cand.labels(key=str(key), candidate="fast").value == \
        pytest.approx(1.0)


# ---------------------------------------------------------------------------
# metric-name static check (wired like check_no_wire_pickle)
# ---------------------------------------------------------------------------

def test_tree_passes_metric_name_check():
    res = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "check_metric_names.py")],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stdout + res.stderr


def test_metric_name_check_catches_offenders(tmp_path):
    bad = tmp_path / "sneaky.py"
    bad.write_text(
        "from paddle_tpu.observability import counter, gauge\n"
        "A = counter('my_unprefixed_total', 'x')\n"
        "B = gauge('paddle_tpu_BadCase', 'x')\n"
        "C = counter('paddle_tpu_dup_total', 'x')\n"
        "D = counter('paddle_tpu_dup_total', 'x')\n")
    res = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "check_metric_names.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 1
    assert "my_unprefixed_total" in res.stdout
    assert "paddle_tpu_BadCase" in res.stdout
    assert "duplicate registration of 'paddle_tpu_dup_total'" \
        in res.stdout

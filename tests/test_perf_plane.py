"""Perf observability plane: cost registry, step attribution, sentinel.

Covers the docs/OBSERVABILITY.md perf-plane acceptance surface: XLA
FLOPs registered for every jitted engine bucket, sampled step-time
breakdowns, MFU on `stats()`/`ping`, the shared bench/perf peak table,
and the perfwatch record/compare/validate regression sentinel.
"""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu.observability import perf, perfwatch
from paddle_tpu.observability import registry as obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# live plane: serving engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def perf_engine():
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving import Engine, GPTDecodeModel
    cfg = GPTConfig.tiny(num_layers=2)
    model = GPTDecodeModel(cfg, seed=0)
    eng = Engine(model, num_slots=4, num_pages=32, page_size=8,
                 max_seq_len=64)
    prev = perf.sampling_every()
    perf.set_every(2)  # sample aggressively so a breakdown lands fast
    try:
        rng = np.random.RandomState(0)
        handles = [eng.submit(rng.randint(0, cfg.vocab_size, (5,)), 8)
                   for _ in range(3)]
        eng.run_until_idle()
        for h in handles:
            h.result(1.0)
        yield cfg, eng
    finally:
        perf.set_every(prev)


def test_cost_registry_covers_every_engine_bucket(perf_engine,
                                                  monkeypatch):
    cfg, eng = perf_engine
    name = f"serving:{eng.engine_id}"
    buckets = set(eng.stats()["compiles"])
    assert buckets  # at least one prefill + one decode program traced
    costs = perf.costs()
    for bucket in buckets:
        assert (name, bucket) in costs, (bucket, sorted(costs))
        assert costs[(name, bucket)]["flops"] > 0, bucket
    # the CPU is no chip the peak table knows: no peak, no MFU, no
    # roofline rows against a guessed device
    assert perf.chip_peak_flops() == (None, "cpu")
    assert perf.chip_peak_bytes_per_s() == (None, "cpu")
    assert perf.roofline() == [] and perf.mfu(1e12, 1.0) == 0.0
    assert perf.snapshot()["peak_flops"] is None
    # with peaks given, the roofline join places every costed bucket
    # against the ridge
    monkeypatch.setenv("TPU_PEAK_TFLOPS_BF16", "197")
    monkeypatch.setenv("TPU_PEAK_GBPS", "819")
    rows = {(r["name"], r["key"]): r for r in perf.roofline()}
    for bucket in buckets:
        row = rows[(name, bucket)]
        assert row["ridge"] > 0
        if row["intensity"] is not None:
            assert row["bound"] in ("compute", "memory")


def test_engine_stats_and_kv_gauge(perf_engine):
    cfg, eng = perf_engine
    st = eng.stats()
    assert st["mfu"] >= 0.0
    assert st["tokens_per_s_per_chip"] >= 0.0
    assert eng._kv_cache_bytes()["paged"] > 0
    # the registry-side gauge reads the same engine via weakref
    dump = {m["name"]: m for m in obs.to_dict()["metrics"]}
    kv = dump["paddle_tpu_perf_kv_cache_bytes"]
    mine = [s for s in kv["samples"]
            if s["labels"].get("engine") == eng.engine_id]
    assert mine and mine[0]["value"] > 0


def test_step_breakdown_sampled(perf_engine):
    cfg, eng = perf_engine
    bd = perf.breakdowns().get(f"engine:{eng.engine_id}")
    assert bd and bd["samples"] >= 1
    assert {"host", "dispatch", "device", "transfer"} <= set(bd["phases"])
    assert all(v >= 0.0 for v in bd["phases"].values())


def test_compile_wall_time_histogram(perf_engine):
    cfg, eng = perf_engine
    dump = {m["name"]: m for m in obs.to_dict()["metrics"]}
    h = dump["paddle_tpu_perf_compile_seconds"]
    by_site = {s["labels"]["site"]: s for s in h["samples"]}
    assert by_site["engine.prefill"]["count"] >= 1
    assert by_site["engine.decode"]["count"] >= 1


def test_ping_reports_mfu_and_per_chip_rate(perf_engine):
    from paddle_tpu.serving import ServingClient, ServingServer
    cfg, eng = perf_engine
    with ServingServer(eng, "127.0.0.1:0") as srv:
        cli = ServingClient(srv.endpoint)
        try:
            info = cli.ping_info()
        finally:
            cli.close()
    assert info["ok"]
    assert info["mfu"] >= 0.0
    assert info["tokens_per_s_per_chip"] >= 0.0


def test_drop_instance_removes_engine_series():
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving import Engine, GPTDecodeModel
    cfg = GPTConfig.tiny(num_layers=1)
    eng = Engine(GPTDecodeModel(cfg, seed=0), num_slots=2, num_pages=16,
                 page_size=8, max_seq_len=32)
    eid, name = eng.engine_id, f"engine:{eng.engine_id}"
    h = eng.submit([1, 2, 3], 2)
    eng.run_until_idle()
    h.result(1.0)

    def series(metric, label, value):
        dump = {m["name"]: m for m in obs.to_dict()["metrics"]}
        return [s for s in dump.get(metric, {}).get("samples", ())
                if s["labels"].get(label) == value]

    assert series("paddle_tpu_perf_mfu", "name", name)
    perf.drop_instance(name, eid)
    assert not series("paddle_tpu_perf_mfu", "name", name)
    assert not series("paddle_tpu_perf_kv_cache_bytes", "engine", eid)


# ---------------------------------------------------------------------------
# live plane: fluid executor
# ---------------------------------------------------------------------------

def test_executor_perf_integration(fresh_programs):
    from paddle_tpu.fluid import Executor, layers, optimizer
    main, startup, scope = fresh_programs
    x = layers.data("x", [-1, 8], "float32")
    loss = layers.mean(layers.fc(x, 8))
    optimizer.SGD(learning_rate=0.01).minimize(loss)
    exe = Executor()
    exe.run(startup)
    prev = perf.sampling_every()
    perf.set_every(1)
    try:
        for _ in range(3):
            exe.run(main, feed={"x": np.ones((4, 8), "float32")},
                    fetch_list=[loss])
    finally:
        perf.set_every(prev)
    costs = perf.costs()
    assert any(n == "executor" and c["flops"]
               for (n, _k), c in costs.items()), sorted(costs)
    bd = perf.breakdowns().get("executor")
    assert bd and {"host", "dispatch", "device", "transfer"} \
        <= set(bd["phases"])
    assert perf.snapshot()["mfu"].get("executor", 0.0) >= 0.0
    dump = {m["name"]: m for m in obs.to_dict()["metrics"]}
    sites = {s["labels"]["site"]: s
             for s in dump["paddle_tpu_perf_compile_seconds"]["samples"]}
    assert sites["executor"]["count"] >= 1


# ---------------------------------------------------------------------------
# MFU convention shared with bench.py
# ---------------------------------------------------------------------------

def test_analytic_flops_and_peak_match_bench(monkeypatch):
    import bench
    from paddle_tpu.models.gpt import GPTConfig
    cfg = GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                    max_position_embeddings=1024)
    b, s = 8, 1024
    bench_fl = bench.gpt_train_flops_per_step(cfg, b, s)
    plane_fl = 3 * perf.analytic_gpt_flops(cfg, b * s, s)  # fwd + 2x bwd
    assert abs(bench_fl - plane_fl) / bench_fl < 0.05
    # one peak table: the bench report and the live gauges agree
    monkeypatch.setenv("TPU_PEAK_TFLOPS_BF16", "275")
    peak, _ = perf.chip_peak_flops()
    assert peak == 275e12
    assert bench.chip_peak_flops()[0] == peak
    assert perf.mfu(peak / 2, 1.0) == pytest.approx(0.5)
    assert perf.mfu(0.0, 1.0) == 0.0 and perf.mfu(1.0, 0.0) == 0.0


# ---------------------------------------------------------------------------
# kernel margins (autobench -> perf)
# ---------------------------------------------------------------------------

def test_autobench_measure_registers_op_costs(monkeypatch):
    import jax.numpy as jnp
    from paddle_tpu.ops import autobench
    monkeypatch.delenv("PADDLE_TPU_AUTOBENCH_CACHE", raising=False)

    def make_args():
        return (jnp.ones((8, 8), jnp.float32),
                jnp.ones((8, 8), jnp.float32))

    key = "perfplane_cost[mm8]"
    win = autobench.prefer(key, {"xla": lambda a, b: a @ b}, make_args,
                           reps=1)
    assert win == "xla"
    assert perf.costs()[("ops:xla", key)]["flops"] > 0


def test_autobench_decision_feeds_kernel_margins():
    from paddle_tpu.ops import autobench
    autobench._record_decision("perfplane_test[s=64]", "pallas",
                               {"pallas": 1e-3, "xla": 1.5e-3})
    k = perf.kernels()["perfplane_test[s=64]"]
    assert k["winner"] == "pallas"
    assert k["margin"] == pytest.approx(1.5)
    assert k["candidates_ms"]["xla"] == pytest.approx(1.5)
    flat = perfwatch._flatten(perf.snapshot())
    med, direction = flat["kernel.perfplane_test[s=64].winner_ms"]
    assert med == pytest.approx(1.0) and direction == "lower"


# ---------------------------------------------------------------------------
# sentinel: record / compare / validate
# ---------------------------------------------------------------------------

def _snap(mfu_val, device_s):
    return {"schema": perf.SNAPSHOT_SCHEMA, "created_unix": 0.0,
            "device_kind": "cpu", "peak_flops": 1.0,
            "peak_bytes_per_s": 1.0, "costs": [], "kernels": {},
            "hbm": {}, "providers": {},
            "mfu": {"engine:e0": mfu_val},
            "breakdown": {"engine:e0": {"samples": 3,
                                        "phases": {"device": device_s}}}}


def test_compare_identical_exits_zero(tmp_path, capsys):
    p = tmp_path / "a.json"
    p.write_text(json.dumps(_snap(0.40, 0.100)))
    assert perfwatch.main(["compare", str(p), str(p)]) == 0
    assert "no regressions" in capsys.readouterr().out


def test_compare_flags_injected_slowdown(tmp_path, capsys):
    old, new = tmp_path / "old.json", tmp_path / "new.json"
    old.write_text(json.dumps(_snap(0.40, 0.100)))
    # ~12% slower device phase, beyond the 5% band and the abs floor
    new.write_text(json.dumps(_snap(0.40, 0.112)))
    assert perfwatch.main(["compare", str(old), str(new)]) == 1
    assert "REGRESSION breakdown.engine:e0.device" \
        in capsys.readouterr().out
    # an MFU drop regresses in the higher-is-better direction
    new.write_text(json.dumps(_snap(0.33, 0.100)))
    assert perfwatch.main(["compare", str(old), str(new)]) == 1
    assert "REGRESSION mfu.engine:e0" in capsys.readouterr().out
    # a widened per-metric tolerance band absorbs both
    new.write_text(json.dumps(_snap(0.33, 0.112)))
    assert perfwatch.main(
        ["compare", str(old), str(new), "--tol-pct", "30"]) == 0


def test_compare_sub_floor_noise_is_not_a_regression(tmp_path):
    old, new = tmp_path / "old.json", tmp_path / "new.json"
    # 50% relative but 0.05ms absolute: under the breakdown floor
    old.write_text(json.dumps(_snap(0.40, 0.0001)))
    new.write_text(json.dumps(_snap(0.40, 0.00015)))
    assert perfwatch.main(["compare", str(old), str(new)]) == 0


def test_compare_tests_flags_2x_slower(tmp_path, capsys):
    po, pn = tmp_path / "o.json", tmp_path / "n.json"
    po.write_text(json.dumps({"schema": "paddle_tpu.test_times/1",
                              "tests": {"t.py::a": 1.0, "t.py::b": 0.5}}))
    pn.write_text(json.dumps({"schema": "paddle_tpu.test_times/1",
                              "tests": {"t.py::a": 2.6, "t.py::b": 0.6}}))
    assert perfwatch.main(["compare", "--tests", str(po), str(pn)]) == 1
    out = capsys.readouterr().out
    assert "SLOWER t.py::a" in out and "t.py::b" not in out
    # identical artifacts pass
    assert perfwatch.main(["compare", "--tests", str(po), str(po)]) == 0


def test_record_snapshot_roundtrip(tmp_path):
    perf.set_mfu("unit:recorder", 0.25)
    try:
        out = tmp_path / "perf.json"
        assert perfwatch.main(["record", "-o", str(out), "--samples",
                               "2", "--interval", "0"]) == 0
        assert perfwatch.validate_file(str(out)) == []
        flat = perfwatch.load_result(str(out))
        med, direction = flat["mfu.unit:recorder"]
        assert med == pytest.approx(0.25) and direction == "higher"
    finally:
        perf.drop_instance("unit:recorder")


def test_bench_record_writer(tmp_path, monkeypatch):
    out = tmp_path / "bench.jsonl"
    monkeypatch.setenv("PADDLE_TPU_BENCH_OUT", str(out))
    rec = {"metric": "unit_test_ms", "value": 1.5, "unit": "ms"}
    perfwatch.finalize_record(rec, "unit_test")
    assert rec["schema"] == perfwatch.BENCH_SCHEMA
    assert rec["config"] == "unit_test"
    perfwatch.finalize_record(
        {"metric": "unit_test_ms", "value": 1.4, "unit": "ms"},
        "unit_test")
    assert perfwatch.validate_file(str(out)) == []
    lines = out.read_text().splitlines()
    assert len(lines) == 2
    assert all(json.loads(ln)["schema"] == perfwatch.BENCH_SCHEMA
               for ln in lines)


def test_repo_bench_artifacts_validate():
    # whatever records the checkout holds (none is fine)
    for path in sorted(glob.glob(os.path.join(REPO, "BENCH_r*.json"))):
        assert perfwatch.validate_file(path) == [], path


def test_check_bench_schema_script():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "check_bench_schema.py")],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "conform" in r.stdout


def test_validate_rejects_malformed(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"schema": "paddle_tpu.bench/1",
                               "metric": "m", "value": None}))
    assert perfwatch.validate_file(str(bad))  # null value, no error note
    unknown = tmp_path / "unknown.json"
    unknown.write_text(json.dumps({"schema": "paddle_tpu.wat/9"}))
    assert perfwatch.validate_file(str(unknown))


# ---------------------------------------------------------------------------
# fleet surfaces: collector summary + top perf pane
# ---------------------------------------------------------------------------

def test_collector_summarize_extracts_perf():
    from paddle_tpu.observability.collector import TelemetryCollector
    dump = {"metrics": [
        {"name": "paddle_tpu_perf_mfu",
         "samples": [{"labels": {"name": "engine:e0"}, "value": 0.4}]},
        {"name": "paddle_tpu_perf_step_breakdown_seconds",
         "samples": [{"labels": {"name": "engine:e0", "phase": "device"},
                      "value": 0.002}]},
        {"name": "paddle_tpu_serving_compiles_total",
         "samples": [{"labels": {"engine": "e0", "bucket": "prefill[8]"},
                      "value": 2.0}]},
        {"name": "paddle_tpu_perf_kv_cache_bytes",
         "samples": [{"labels": {"engine": "e0"}, "value": 1024.0}]},
        {"name": "paddle_tpu_autobench_candidate_ms",
         "samples": [{"labels": {"key": "attn", "candidate": "pallas"},
                      "value": 1.0}]},
    ]}
    out = TelemetryCollector._summarize(None, {}, dump)
    summary = out["perf"]
    assert summary["mfu"] == {"engine:e0": 0.4}
    assert summary["breakdown"] == {"engine:e0/device": 0.002}
    assert summary["compiles_total"] == 2.0
    assert summary["kv_cache_bytes"] == 1024.0
    assert summary["kernel_ms"] == {"attn/pallas": 1.0}


def test_render_perf_pane():
    from paddle_tpu.observability import top
    fleet = {"procs": [{"role": "serving", "host": "h", "pid": 1,
                        "summary": {"perf": {
                            "mfu": {"engine:e0": 0.41},
                            "breakdown": {"engine:e0/device": 0.002,
                                          "engine:e0/host": 0.001},
                            "compiles_total": 4,
                            "hbm": {"in_use": 2 ** 30, "limit": 2 ** 31},
                            "kv_cache_bytes": 2 ** 20,
                            "kernel_ms": {"attn[s]/pallas": 1.0,
                                          "attn[s]/xla": 1.5}}}}]}
    text = top.render_perf(fleet)
    assert "engine:e0" in text
    assert "0.41" in text
    assert "device=2.00ms" in text
    assert "pallas=1.000*" in text  # winner starred
    assert "no perf data" in top.render_perf({"procs": []})

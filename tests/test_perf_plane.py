"""Perf observability plane: cost registry, step attribution, MFU.

Covers the docs/OBSERVABILITY.md perf-plane acceptance surface: XLA
FLOPs registered for every jitted engine bucket, sampled step-time
breakdowns, MFU on `stats()`/`ping`, and the FLOP convention shared
with the benchmark's training cells (benchmark/lib/peaks.py).
"""
import numpy as np
import pytest

from paddle_tpu.observability import perf
from paddle_tpu.observability import registry as obs


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
    # the CPU is no chip the peak table knows: no peak and no MFU
    # against a guessed device
    assert perf.chip_peak_flops() == (None, "cpu")
    assert perf.chip_peak_bytes_per_s() == (None, "cpu")
    assert perf.mfu(1e12, 1.0) == 0.0
    # with peaks given, both tables answer for the same device kind
    monkeypatch.setenv("TPU_PEAK_TFLOPS_BF16", "197")
    monkeypatch.setenv("TPU_PEAK_GBPS", "819")
    assert perf.chip_peak_flops() == (197e12, "cpu")
    assert perf.chip_peak_bytes_per_s() == (819e9, "cpu")


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
    dump = {m["name"]: m for m in obs.to_dict()["metrics"]}
    # a sampled step sets the gauge; the CPU has no peak, so it reads 0
    assert [s["value"] for s in dump["paddle_tpu_perf_mfu"]["samples"]
            if s["labels"].get("name") == "executor"] == [0.0]
    sites = {s["labels"]["site"]: s
             for s in dump["paddle_tpu_perf_compile_seconds"]["samples"]}
    assert sites["executor"]["count"] >= 1


# ---------------------------------------------------------------------------
# MFU convention shared with the training cells (benchmark/lib/peaks.py)
# ---------------------------------------------------------------------------

def test_analytic_flops_match_the_training_cells(monkeypatch):
    from benchmark.lib import peaks
    from paddle_tpu.models.gpt import GPTConfig
    cfg = GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                    max_position_embeddings=1024)
    sizes = {"hidden_size": cfg.hidden_size, "num_layers": cfg.num_layers,
             "vocab_size": cfg.vocab_size,
             "intermediate_size": cfg.intermediate_size}
    b, s = 8, 1024
    cell_fl = peaks.gpt_train_flops_per_step(sizes, b, s)
    plane_fl = 3 * perf.analytic_gpt_flops(cfg, b * s, s)  # fwd + 2x bwd
    assert abs(cell_fl - plane_fl) / cell_fl < 0.05
    monkeypatch.setenv("TPU_PEAK_TFLOPS_BF16", "275")
    peak, _ = perf.chip_peak_flops()
    assert peak == 275e12
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
    assert k["candidates_ms"]["pallas"] == pytest.approx(1.0)


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

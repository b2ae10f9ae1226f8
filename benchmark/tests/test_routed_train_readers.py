"""The readers of the trained routed cell (readers/routed_train.py) and the
counts behind them (lib/routed_train_counts.py) give known answers: hand
figures at Mellum2's sizes, a recorded trace of two steps on the chip
(tests/data/mellum_two_steps.json.gz, cut by tools/routed_train_ops.py
--cut from PR 46's first traced run), and the whole cell rehearsed on the
CPU. They take a Pallas call by its kernel's name and return None where
there is nothing to read (a parent without the model)."""
import gzip
import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import routed_train_counts as counts
from benchmark.lib.trace import Reduced
from benchmark.readers import device, routed_train as rt
from benchmark.runners.train_routed import sizes_of

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "mellum2_12b_a2p5b_train.b2s8192"
NEW = ("mfu.routed", "flash_band_roofline", "flash_full_gqa_roofline",
       "train_expert_roofline", "train_expert_device_share",
       "train_band_attn_device_share", "train_full_attn_device_share",
       "adamw_device_share", "train_pairs_held_share",
       "train_expert_load_max_over_mean")
SHARED = ("step_p50_ms", "train_attn_device_share", "gate_keys_pallas.train",
          "train_device_idle_share", "peak_hbm_gib.train",
          "train_dispatch_ms_p50")


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2_12b_a2p5b_train.json")) as f:
        cfg = json.load(f)
    cfg["sizes"] = sizes_of(cfg)
    return cfg


def _spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def _run(tally=True):
    with gzip.open(os.path.join(HERE, "data", "mellum_two_steps.json.gz"),
                   "rt") as f:
        trace = Reduced(json.load(f)["trace"])
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "b2s8192.json")) as f:
        traffic = json.load(f)
    steps = 80
    run = {"trace": trace, "config": _config(), "traffic": traffic,
           "device_kind": "TPU v5 lite", "chips": 1, "kind": "train",
           "step_seconds": [0.556] * 10}
    if tally:
        run["tally"] = {
            "pairs_routed": 4 * 131072 * steps,
            "pairs_held": 4 * 32768 * steps,
            "experts_held": list(range(16)),
            "held_counts": [[1024 * steps] * 8 + [3072 * steps] * 8] * 4}
    return run


def test_counts_against_hand_figures():
    s = _config()["sizes"]
    # ISSUE 46's arithmetic
    assert counts.attention_params(s) == 21_233_664 + 256
    assert counts.expert_params(s) == 6_193_152
    assert counts.layer_params(s) == 120_476_416
    assert counts.weight_params(s) == 595_154_176
    whole = dict(s, experts_held=None)
    assert counts.layer_params(whole) == 417_747_712
    # what a mask lets through: the triangle, and the band inside it
    assert counts.allowed_pairs(8192, None) == 8192 * 8193 // 2
    assert counts.allowed_pairs(8192, 1024) == \
        1024 * 1025 // 2 + 7168 * 1024
    assert counts.allowed_pairs(64, 1024) == 64 * 65 // 2
    f = counts.forward_flops(s, 2, 8192, 4 * 32768)
    tf = {k: round(v / 1e12, 2) for k, v in f.items()}
    assert tf == {"projections": 2.78, "router": 0.02, "attention": 1.87,
                  "experts": 1.62, "head": 1.86}
    assert round(counts.step_flops(s, 2, 8192, 4 * 32768) / 1e12, 1) == 24.5
    assert round(counts.flash_call_flops(
        s, 2, 8192, counts.FULL, "fwd") / 1e12, 2) == 1.10
    assert round(counts.flash_call_flops(
        s, 2, 8192, counts.SLIDING, "fwd") / 1e12, 2) == 0.26
    # dq is three products to the forward's two, dk/dv four
    assert counts.flash_call_flops(s, 2, 8192, counts.FULL, "dkv") == \
        2 * counts.flash_call_flops(s, 2, 8192, counts.FULL, "fwd")
    # a grouped product over a layer's 32,768 held pairs: 135 GFLOP, and
    # 66 MB of weights beside 210 MB of rows
    assert round(counts.expert_product_flops(s, 32768) / 1e9) == 135
    assert round(counts.expert_product_bytes(s, 32768) / 1e6) == 276


def test_the_programs_weights_are_the_counted_ones():
    import jax
    from benchmark.runners.train_routed import model_config
    from paddle_tpu.models import mellum
    cfg = _config()
    import math
    n = sum(math.prod(sh) for sh in jax.tree_util.tree_leaves(
        mellum.param_shapes(model_config(cfg)),
        is_leaf=lambda x: isinstance(x, tuple)))
    assert n == counts.weight_params(cfg["sizes"]) == 595_154_176


def test_the_recorded_steps_read_as_the_chip_gave_them():
    run = _run()
    read = lambda name: getattr(rt, _spec(name)["reader"].split(":")[1])(
        run, **_spec(name).get("args", {}))
    got = {name: read(name) for name in NEW}
    assert all(v is not None for v in got.values()), got
    # every share and roofline under its maximum, above nothing
    for name in NEW:
        mx = _spec(name).get("max")
        assert got[name] > 0 and (mx is None or got[name] < mx), name
    assert got["train_pairs_held_share"] == 25.0
    assert got["train_expert_load_max_over_mean"] == 1.5
    # PR 46's first traced run: a step of 554 ms; the band's calls at two
    # fifths of the peak, the full layer's at two thirds
    assert 20 < got["mfu.routed"] < 25
    assert 35 < got["flash_band_roofline"] < 45
    assert 55 < got["flash_full_gqa_roofline"] < 70
    assert 5 < got["train_expert_device_share"] < 12
    assert 2 < got["adamw_device_share"] < 8
    # the two kinds' shares add up to the accepted metric's, which takes
    # every flash call by its output's shape
    both = got["train_band_attn_device_share"] \
        + got["train_full_attn_device_share"]
    spec = _spec("train_attn_device_share")
    assert device.op_device_share(run, **spec["args"]) == \
        pytest.approx(both, rel=1e-6)
    # six calls a layer and step: forward, its recomputation, dq, dk/dv
    calls, _ = rt._flash_calls(run, "band")
    assert len(calls) == 2 * 3 * 4 and \
        sorted(set(c for c, _s in calls)) == ["dkv", "dq", "fwd"]
    calls, _ = rt._flash_calls(run, "full")
    assert len(calls) == 2 * 4
    secs, _ = rt._grouped(run)
    assert len(secs) == 2 * 4 * (counts.GMM_CALLS + counts.TGMM_CALLS)


def test_nothing_to_read_gives_none():
    run = _run(tally=False)
    for name in ("mfu.routed", "train_expert_roofline",
                 "train_pairs_held_share",
                 "train_expert_load_max_over_mean"):
        spec = _spec(name)
        fn = getattr(rt, spec["reader"].split(":")[1])
        assert fn(run, **spec.get("args", {})) is None
    bare = dict(run, trace=None)
    for name in NEW:
        spec = _spec(name)
        fn = getattr(rt, spec["reader"].split(":")[1])
        assert fn(bare, **spec.get("args", {})) is None, name
    # a trace of another program (GPT's training step has no such call)
    other = dict(_run(), trace=Reduced({"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 10, 50]]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.train_step", 0, 100]]}]}]}))
    for name in ("flash_band_roofline", "flash_full_gqa_roofline",
                 "train_expert_device_share", "adamw_device_share"):
        spec = _spec(name)
        fn = getattr(rt, spec["reader"].split(":")[1])
        assert fn(other, **spec.get("args", {})) is None, name


def test_benchmark_json_lists_the_cell_where_it_reports():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "train_tok_s_chip"
        if per_layer[name]["unit"] == "%":
            assert _spec(name)["max"] == 100
    for name in SHARED:
        assert CELL in per_layer[name]["workloads"]
    # its reader counts GPT's FLOPs; a whole triangle for every call
    assert CELL not in per_layer["mfu"]["workloads"]
    assert CELL not in per_layer["flash_attn_roofline"]["workloads"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["train_tok_s_chip"]["workloads"]


def test_the_cell_rehearses_and_would_report_its_counters():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearse.py"),
         "--workload", CELL, "--trace", "1"], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    for name in ("train_pairs_held_share", "train_expert_load_max_over_mean",
                 "step_p50_ms", "train_dispatch_ms_p50"):
        assert name in line["would_report"]

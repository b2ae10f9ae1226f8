"""The readers of the hybrid cell (readers/hybrid.py) give known answers:
on a hand-made trace whose answers are plain, and on two decode steps
recorded on the chip (tests/data/lfm2_two_steps.json). They look at the
decode programs only, count a `while`'s body once, and return None where
there is nothing to read."""
import json
import os
import types

import pytest

from benchmark.lib import hybrid_counts
from benchmark.lib.trace import Reduced
from benchmark.readers import hybrid
from benchmark.runners.serve_hybrid import sizes_of

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "lfm2_8b_a1b_serve.decode_closed128"
PEAK = 819e9


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_8b_a1b_serve.json")) as f:
        cfg = json.load(f)
    cfg["sizes"] = sizes_of(cfg)
    return cfg


def _spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def _made():
    """Two decode programs of 100 us and a prefill between them. In each
    decode: a grouped product 30 us, the paged kernel 20 us, an
    in-projection 5 us, sampling 40 us. The prefill holds a grouped product
    too (300 us), which no reader may count."""
    w1 = "bf16[32,2048,1792]{2,1,0} %params__layers___2___ffn____w1__.1"
    dec = lambda t: [
        [f"%fusion.1 = bf16[32,64,1792]{{2,1,0}} fusion({w1})", t, 30_000],
        ["%decode.3 = bf16[64,4,8,128]{3,2,1,0} custom-call(s32[64,256] %x)",
         t + 30_000, 20_000],
        ["%fusion.2 = bf16[64,1,6144]{2,0,1} fusion(bf16[2048,6144] %w)",
         t + 50_000, 5_000],
        ["%fusion.14 = f32[4194304]{0} fusion(f32[64,65536] %lg)",
         t + 55_000, 40_000]]
    ops = dec(0) + [[f"%ragged-dot.1 = bf16[2048,1792] custom-call({w1})",
                     100_000, 300_000]] + dec(400_000)
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [
            ["jit_decode(1)", 0, 100_000], ["jit_prefill(2)", 100_000, 300_000],
            ["jit_decode(1)", 400_000, 100_000]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ["bench.step", 0, 400_000], ["bench.step", 400_000, 110_000]]}]}
    return {"planes": [dev, host]}


def _run(trace, touched=None, ctx=None):
    run = {"trace": Reduced(trace), "config": _config(), "traffic": {},
           "device_kind": "TPU v5 lite", "trace_span": (0.0, 1.0)}
    if touched is not None:
        zero = [[0] * 32 for _ in range(12)]
        run["stats_log"] = [
            {"at": "", "expert_tokens": zero, "expert_touched": zero},
            {"at": "trace_start", "expert_tokens": zero,
             "expert_touched": zero},
            {"at": "trace_end",
             "expert_tokens": [[3] * 31 + [35]] * 12,
             "expert_touched": [[touched] * 32] * 12}]
    if ctx is not None:
        run["loop"] = types.SimpleNamespace(
            steps=[(0.1, 0.2, 0, 64, ctx, 0), (0.3, 0.4, 0, 64, ctx, 0),
                   (2.0, 2.1, 0, 64, 10**9, 0)])     # the last: not traced
    return run


def test_known_answers_on_a_made_trace():
    run = _run(_made(), touched=2, ctx=50_000)
    moe = hybrid.decode_op_share(run, **_spec("moe_device_share")["args"])
    assert moe == pytest.approx(100 * 60 / 190)      # 2 x 30 of 2 x 95 us
    conv = hybrid.decode_op_share(run, **_spec("conv_device_share")["args"])
    assert conv == pytest.approx(100 * 10 / 190)
    # every expert of 12 layers in both steps: 768 experts' weights in 60 us
    need = 12 * 32 * 2 * hybrid_counts.expert_weight_bytes(2048, 1792)
    assert hybrid.moe_expert_roofline(
        run, **_spec("moe_expert_roofline")["args"]) == pytest.approx(
            100 * need / PEAK / 60e-6)
    # 2 steps x 50,000 live tokens x 2 x 8 x 64 x 2 B x 3 layers in 40 us
    need = hybrid_counts.gqa_kv_bytes(100_000, 8, 64, 3)
    assert need == 100_000 * 2048 * 3
    assert hybrid.paged_attn_gqa_roofline(
        run, **_spec("paged_attn_gqa_roofline")["args"]) == pytest.approx(
            100 * need / PEAK / 40e-6)
    # the busiest expert of each layer has 35 of a mean of 4
    assert hybrid.expert_load_max_over_mean(run) == pytest.approx(35 / 4)
    # sampling: 2 x 40 us; the head's product makes [S,V] logits too, from
    # the embedding matrix, and is the model's, not the sampler's
    sam = _spec("sampler_device_share")["args"]
    assert hybrid.decode_op_share(run, **sam) == pytest.approx(100 * 80 / 190)
    t = _made()
    t["planes"][0]["lines"][0]["events"] += [
        ["%fusion.640 = f32[64,65536]{1,0} fusion(bf16[65536,2048]{1,0} "
         "%params__embed__.1)", at + 95_000, 5_000] for at in (0, 400_000)]
    assert hybrid.decode_op_share(_run(t), **sam) == \
        pytest.approx(100 * 80 / 200)


def test_nothing_to_read_gives_none():
    bare = {"config": _config(), "traffic": {}, "device_kind": "TPU v5 lite"}
    assert hybrid.decode_op_share(bare, ops=["x"]) is None
    assert hybrid.moe_expert_roofline(bare, ops=["x"]) is None
    assert hybrid.paged_attn_gqa_roofline(bare, ops=["x"]) is None
    assert hybrid.expert_load_max_over_mean(bare) is None
    # a trace, but a program without the tallies (the parent): no roofline
    run = _run(_made(), ctx=50_000)
    assert hybrid.moe_expert_roofline(
        run, **_spec("moe_expert_roofline")["args"]) is None
    # a trace with no decode program in it
    t = _made()
    t["planes"][0]["lines"][1]["events"] = [["jit_prefill(2)", 0, 500_000]]
    assert hybrid.decode_op_share(
        _run(t), **_spec("moe_device_share")["args"]) is None


def test_the_new_metrics_files_are_whole():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    hybrids = {"moe_device_share", "moe_expert_roofline",
               "conv_device_share", "paged_attn_gqa_roofline",
               "expert_load_max_over_mean", "sampler_device_share"}
    # readings the GPT cells have under a name that moves `itl_p95_ms`,
    # which this cell does not report: the same readers, moving `out_tok_s`
    twins = {"decode_device_ms", "prefill_device_ms_ktok",
             "decode_step_p50_ms", "step_host_share", "device_idle_share",
             "gate_keys_pallas"}
    assert {m["name"] for m in mine} == \
        hybrids | {n + ".tput" for n in twins}
    for m in mine:
        spec = _spec(m["name"])
        assert (spec["unit"], spec["layer"], spec["moves"]) == \
            (m["unit"], m["layer"], "out_tok_s")
        if m["name"] in hybrids:
            assert spec["reader"].startswith("hybrid:")
            if m["unit"] == "%":
                assert spec["max"] == 100 and "catches" in spec
        else:
            name = m["name"][:-len(".tput")]
            old = _spec(name if name != "gate_keys_pallas"
                        else name + ".serve")
            assert (spec["reader"], spec.get("args"), spec.get("max")) == \
                (old["reader"], old.get("args"), old.get("max"))
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert not listed & {"paged_attn_roofline", "attn_device_share"}


DATA = os.path.join(HERE, "data", "lfm2_two_steps.json")


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_known_answers_on_a_recorded_trace():
    with open(DATA) as f:
        doc = json.load(f)
    want = doc["expect"]
    run = _run(doc["trace"], touched=2, ctx=want["ctx_tokens_a_step"])
    for name in ("moe_device_share", "conv_device_share",
                 "sampler_device_share"):
        assert hybrid.decode_op_share(run, **_spec(name)["args"]) == \
            pytest.approx(want[name], rel=1e-9)
    assert hybrid.moe_expert_roofline(
        run, **_spec("moe_expert_roofline")["args"]) == pytest.approx(
            want["moe_expert_roofline"], rel=1e-9)
    assert hybrid.paged_attn_gqa_roofline(
        run, **_spec("paged_attn_gqa_roofline")["args"]) == pytest.approx(
            want["paged_attn_gqa_roofline"], rel=1e-9)
    # and what the numbers must say whatever their digits: the grouped
    # products run near the bandwidth's roof, no share passes the whole
    assert 50 < want["moe_expert_roofline"] <= 100
    assert want["moe_device_share"] + want["conv_device_share"] \
        + want["sampler_device_share"] < 100
    assert want["sampler_device_share"] > 50    # PERF.md section 5

"""The readers of the Xing4.0 cell (readers/hyper.py) and the counts behind
them (lib/hyper_counts.py) give known answers: hand figures at the
published sizes, a hand-made trace whose answers are plain, the files that
declare them, and the whole cell rehearsed on the CPU. They look inside
the programs of one kind and return None where there is nothing to read (a
parent without the streams, an untraced run)."""
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import hyper_counts
from benchmark.lib.trace import Reduced
from benchmark.readers import hyper
from benchmark.runners.serve_mla_hyper import sizes_of

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "xing4_29b_a4b_serve.longin_closed64"
PEAK = 819e9
NEW = ("hyper_prefill_device_share", "hyper_decode_device_share",
       "hyper_mix_roofline", "hyper_sinkhorn_iters")


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4_29b_a4b_serve.json")) as f:
        cfg = json.load(f)
    cfg["sizes"] = sizes_of(cfg)
    return cfg


def _spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_counts_against_hand_figures():
    s = _config()["sizes"]
    assert hyper_counts.stream_width(s) == 4 * 3584 == 14336
    # read 4 streams, write 4, h out, F(h) in: 10 x 3584 numbers in bf16
    assert hyper_counts.mix_bytes_a_token(s) == 71_680
    assert hyper_counts.sublayers(s) == 12
    assert hyper_counts.mix_bytes(1, s) == 860_160          # 0.86 MB a token
    # 1.05 ms a 1,000 tokens at the peak; a 16,384 bucket's sub-layer 1.43
    assert round(hyper_counts.mix_bytes(1000, s) / PEAK * 1e3, 2) == 1.05
    assert round(16384 * 71_680 / PEAK * 1e3, 2) == 1.43


X = "bf16[1,4096,3584]{2,1,0:T(8,128)(2,1)}"
SINK = "(" + ", ".join(["f32[4096]{0:T(1024)}"] * 16) + ")"


def _made():
    """A prefill program of 1,000 us between two decode programs of 100.
    In the prefill: the product with phi over one stream (40 us), the
    read mix (60), a Sinkhorn step (10, inside a `while` of 25 that has 15
    of its own), a write fusion (100), the flash kernel (300) and an
    expert product (400). In each decode: a write fusion at 32 rows (8), a
    Sinkhorn step (2), the latent kernel (50)."""
    pre = [
        ["%fusion.33 = f32[24,4096]{1,0:T(8,128)} fusion(" + X + " %x0, "
         "bf16[3584,24]{1,0} %get-tuple-element.136)", 100_000, 40_000],
        ["%fusion.9 = " + X + " fusion(f32[4,4096]{1,0} %pre, " + X
         + " %x0, " + X + " %x1, " + X + " %x2, " + X + " %x3)", 140_000,
         60_000],
        ["%while.2 = (s32[], " + SINK[1:-1] + ") while(%tuple.104)",
         200_000, 25_000],
        ["%multiply_divide_fusion.19 = " + SINK + " fusion(" + SINK[1:-1]
         + " %gte)", 205_000, 10_000],
        ["%fusion.5 = (" + X + ", " + X + ") fusion(" + X + " %x0, " + X
         + " %x1, " + X + " %x2, " + X + " %x3, " + X
         + " %f, f32[4096]{0} %r00)", 225_000, 100_000],
        # a product that names three arrays 3,584 wide: not the streams'
        ["%fusion.77 = " + X + " fusion(bf16[1,4096,4096]{2,1,0} %a, "
         "bf16[4096,3584]{1,0} %wo, " + X + " %bias)", 1_030_000, 1_000],
        ["%flash.1 = bf16[32,4096,128]{2,1,0} custom-call("
         "bf16[32,4096,192] %q)", 325_000, 300_000],
        ["%gmm.7 = bf16[16384,1024]{1,0} custom-call(bf16[16384,3584] %xs, "
         "bf16[64,3584,1024]{2,1,0} %params__layers___1___ffn____w1__)",
         625_000, 400_000]]
    dec = lambda t: [
        ["%fusion.5 = (bf16[32,3584]{1,0}, bf16[32,3584]{1,0}) fusion("
         + ", ".join(f"bf16[32,3584]{{1,0}} %x{j}" for j in range(4))
         + ", bf16[32,3584]{1,0} %f, f32[32]{0} %r00)", t, 8_000],
        ["%multiply_divide_fusion.19 = ("
         + ", ".join(["f32[32]{0:T(128)}"] * 16) + ") fusion(f32[32]{0} %g)",
         t + 8_000, 2_000],
        ["%decode.3 = bf16[32,32,512]{2,1,0} custom-call(s32[32,262] %pt, "
         "bf16[6,5121,64,640] %pool)", t + 10_000, 50_000]]
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": dec(0) + pre + dec(1_100_000)},
        {"name": "XLA Modules", "events": [
            ["jit_decode(1)", 0, 100_000],
            ["jit_prefill(2)", 100_000, 1_000_000],
            ["jit_decode(1)", 1_100_000, 100_000]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ["bench.step", 0, 1_100_000], ["bench.step", 1_100_000, 110_000]]}]}
    return {"planes": [dev, host]}


def _run(trace=None, config=None):
    run = {"config": config or _config(), "traffic": {}, "kind": "serve",
           "device_kind": "TPU v5 lite", "trace_span": (0.0, 1.0),
           "window": (0.0, 1.0)}
    if trace is not None:
        run["trace"] = Reduced(trace)
        # (t0, t1, first tokens, decoded, context read, prompt tokens)
        run["loop"] = types.SimpleNamespace(
            steps=[(0.1, 0.2, 1, 31, 10**5, 3000), (0.3, 0.4, 0, 32, 10**5, 0),
                   (2.0, 2.1, 1, 31, 10**5, 9999)])     # the last: not traced
    return run


def test_known_answers_on_a_made_trace():
    run = _run(_made())
    pre = hyper.program_op_share(
        run, **_spec("hyper_prefill_device_share")["args"])
    # the product, the read, the loop's step, the write; not the `while`'s
    # own 15 us, the flash kernel or the experts' product
    busy = 40 + 60 + 25 + 100 + 1 + 300 + 400
    assert pre == pytest.approx(100 * (40 + 60 + 10 + 100) / busy)
    dec = hyper.program_op_share(
        run, **_spec("hyper_decode_device_share")["args"])
    assert dec == pytest.approx(100 * (8 + 2) / 60)
    # 3,000 prompt tokens x 860,160 B in 210 us: over the roofline on
    # purpose, the reader does not clip (the harness fails such a run)
    roof = hyper.hyper_mix_roofline(run, **_spec("hyper_mix_roofline")["args"])
    assert roof == pytest.approx(100 * 3000 * 860_160 / PEAK / 210e-6)


def test_the_patterns_meet_the_names_the_chips_compiler_gives():
    """tests/data/xing_compiled_ops.json.gz: the operations of the cell's
    decode program and of its 4,096 prefill bucket as compiled for a
    DESCRIBED v5e at the published sizes, named as a profiler names them
    (scripts/pr49_compiled_ops.py over scripts/pr49_compile_for_v5e.py
    --text; no chip, nothing ran). The files' patterns find each kind of
    the streams' operations there, 12 sub-layers of them, and none of the
    kernels, the experts' products or the sampler."""
    import gzip
    import re
    with gzip.open(os.path.join(HERE, "data",
                                "xing_compiled_ops.json.gz")) as f:
        programs = json.loads(f.read())
    args = _spec("hyper_decode_device_share")["args"]
    assert args["ops"] == _spec("hyper_prefill_device_share")["args"]["ops"] \
        == _spec("hyper_mix_roofline")["args"]["ops"]
    fields = hyper._fields({"config": _config()})
    rx = [re.compile(p.format(**fields)) for p in args["ops"]]
    no = [re.compile(p.format(**fields)) for p in args["but"]]
    for program, rows in (("decode_32262.hlo.txt", 32),
                          ("prefill_4096.hlo.txt", 4096)):
        names = [n for n, _cycles in programs[program]]
        hit = [n for n in names if any(r.search(n) for r in rx)
               and not any(r.search(n) for r in no)]
        by = [sum(1 for n in hit if r.search(n)) for r in rx]
        # the mixes (five streams' worth of arrays in one operation): a
        # read and two writes a sub-layer at least; phi's product four
        # times a sub-layer (some fused in pairs); the loop's three fusions
        assert by[0] >= 12 * 2 and by[1] >= 12 * 2 and by[4] >= 12 * 3, by
        assert not [n for n in hit if "custom-call" in n
                    and "tpu_custom_call" in n]
        assert not [n for n in hit if re.search(r"\[64,3584,1024\]", n)]
        assert not [n for n in hit if "131072" in n]
        # the sampler's passes give tuples of float32 vectors too: `but`
        if rows == 32:
            assert [n for n in names if rx[4].search(n) and "131072" in n]
        assert 0 < len(hit) < len(names) / 2


def test_known_answers_on_programs_recorded_on_the_chip():
    """tests/data/xing_prefill_two_steps.json.gz: a prefill of the 2,048
    bucket and the two decode programs after it, cut from the cell's trace
    on the chip (scripts/pr49_cut_trace.py), and what the readers and the
    files' patterns made of it when it was recorded."""
    import gzip
    with gzip.open(os.path.join(
            HERE, "data", "xing_prefill_two_steps.json.gz")) as f:
        rec = json.loads(f.read())
    want = rec["expect"]
    run = _run(rec["trace"])
    for name in NEW[:2]:
        got = hyper.program_op_share(run, **_spec(name)["args"])
        assert got == pytest.approx(want[name]), name
    # the streams' steps: a quarter of a short prefill, a thirtieth of a
    # 32-row decode (the loop's launches are 1%)
    assert 15 < want["hyper_prefill_device_share"] < 35
    assert 1 < want["hyper_decode_device_share"] < 8
    # 2,048 prompt tokens through 12 sub-layers, by the bytes' floor
    run["loop"] = types.SimpleNamespace(
        steps=[(0.1, 0.2, 1, 31, 10**5, 2048)])
    roof = hyper.hyper_mix_roofline(run, **_spec(NEW[2])["args"])
    assert 5 < roof < 100


def test_nothing_to_read_gives_none():
    spec = _spec("hyper_prefill_device_share")["args"]
    assert hyper.program_op_share(_run(), **spec) is None       # no trace
    assert hyper.hyper_mix_roofline(_run(), **spec) is None
    # a configuration of one stream (the parent's kanana): nothing
    one = _config()
    one["sizes"] = dict(one["sizes"], hc_mult=None)
    assert hyper.program_op_share(_run(_made(), one), **spec) is None
    # programs that hold no such operation
    assert hyper.program_op_share(_run(_made()), program="^jit_prefill",
                                  ops=["no such op"]) is None
    assert hyper.program_op_share(_run(_made()), program="^jit_no_such",
                                  ops=spec["ops"]) is None


def _loops(turns):
    """A decode program that holds one Sinkhorn loop for each of `turns`:
    a `while` over 16 float32 vectors whose body is three instructions,
    each launched once a turn, and after them a sampler's loop of 9 turns
    over other arrays."""
    vec = ", ".join(["f32[1,32]{1,0:T(1,128)S(1)}"] * 16)
    ops, t = [], 1_000
    for k, n in enumerate(turns):
        ops.append([f"%while.{k} = (s32[]{{:T(128)}}, {vec}) while(%t.{k})",
                    t, 30 * n + 20])
        for i in range(n):
            for j in range(3):
                ops.append([f"%multiply_divide_fusion.{3 * k + j} = ({vec}) "
                            f"fusion(%g)", t + 10 + 30 * i + 10 * j, 8])
        t += 30 * n + 100
    ops.append(["%while.99 = (s32[]{:T(128)}, u32[32]{0:T(128)}, "
                "f32[32,131072]{1,0}) while(%t.99)", t, 200])
    ops += [[f"%fusion.7 = f32[32]{{0}} fusion(%p)", t + 10 + 20 * i, 10]
            for i in range(9)]
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_decode(1)", 0, t + 300]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ["bench.step", 0, t + 300]]}]}
    return {"planes": [dev, host]}


def test_the_iterations_are_counted_on_the_device():
    """What the program RAN, not what its configuration says: the launches
    of one instruction inside one Sinkhorn `while`, the least over the
    loops; the sampler's loop is none of them."""
    args = _spec("hyper_sinkhorn_iters")["args"]
    assert hyper.hyper_sinkhorn_iters(_run(_loops([20, 20])), **args) == 20
    assert hyper.hyper_sinkhorn_iters(_run(_loops([20, 7, 20])), **args) == 7
    # a body's launch that the profiler puts past the loop's end
    late = _loops([20])
    ops = late["planes"][0]["lines"][0]["events"]
    ops[-12][1] = ops[0][1] + ops[0][2] - 4     # the last turn's last launch
    assert hyper.hyper_sinkhorn_iters(_run(late), **args) == 20
    # no such loop (one turn: the compiler unrolls it), no trace, one stream
    assert hyper.hyper_sinkhorn_iters(_run(_loops([])), **args) is None
    assert hyper.hyper_sinkhorn_iters(_run(), **args) is None
    one = _config()
    one["sizes"] = dict(one["sizes"], hc_mult=None)
    assert hyper.hyper_sinkhorn_iters(_run(_loops([20]), one), **args) is None
    # the programs recorded on the chip: 12 loops a program, 20 turns each
    import gzip
    with gzip.open(os.path.join(
            HERE, "data", "xing_prefill_two_steps.json.gz")) as f:
        rec = json.loads(f.read())
    assert hyper.hyper_sinkhorn_iters(_run(rec["trace"]), **args) == 20
    for program in ("^jit_prefill", "^jit_decode"):
        assert hyper.hyper_sinkhorn_iters(
            _run(rec["trace"]), program, args["loop"]) == 20


def test_the_new_metrics_are_declared_with_their_files_and_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        spec, m = _spec(name), declared[name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert (spec["unit"], spec["layer"], spec["source"]) == \
            (m["unit"], m["layer"], m["source"])
        assert "catches" in spec
        mod, fn = spec["reader"].split(":")
        assert mod == "hyper" and callable(getattr(hyper, fn))
    for name in NEW[:3]:
        assert _spec(name)["max"] == 100
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longin_closed64"
    entry = next(c for c in bench["configs"]
                 if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "num_nextn_predict_layers"]
    out = next(m for m in bench["end_to_end"] if m["name"] == "out_tok_s")
    assert CELL in out["workloads"]


def test_the_configuration_holds_every_published_key_of_the_catalog():
    """The catalog's `config` of Xing4.0-29B-A4B, key for key (copied
    here: the catalog is not in the repo); three keys cut, each listed."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "max_position_embeddings": 262144, "model_type": "xing4_0",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "q_lora_rank": 768, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    cfg = _config()
    cut = {"num_hidden_layers": 6, "first_k_dense_replace": 1,
           "num_nextn_predict_layers": 0}
    assert cfg["reduced"] == list(cut)
    for key, want in published.items():
        assert cfg[key] == cut.get(key, want), key
        if key in cut:
            assert cfg["published"][key] == want
    # the traffic, letter for letter
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "longin_closed64.json")) as f:
        tr = json.load(f)
    assert (tr["clients"], tr["primers"], tr["epoch"], tr["ramp_s"]) == \
        (64, 32, 64, 0.0)
    assert tr["prompt"] == {"dist": "lognormal", "median": 6144,
                            "sigma": 0.6, "min": 2048, "max": 16384}
    assert tr["output"] == {"dist": "lognormal", "median": 96, "sigma": 0.6,
                            "min": 32, "max": 384}
    assert tr["sampling"]["greedy_every"] == 2
    assert (tr["order"], tr["pairing_key"]) == ("file", 4901)
    eng = cfg["engine"]
    assert eng["max_seq_len"] >= 16384 + 384


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearse.py"),
         "--workload", CELL, "--trace", "1", "--seconds", "3"],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0
    for name in ("batch_occupancy", "expert_load_max_over_mean",
                 "out_tok_s_slice_p50"):
        assert name in line["would_report"], name

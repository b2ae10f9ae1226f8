"""The readers of the latent cell (readers/latent.py) and the counts behind
them (lib/latent_counts.py) give known answers: hand figures at
kanana-2-30b-a3b's sizes, a hand-made trace whose answers are plain, and
the whole cell rehearsed on the CPU. They look at the decode programs
only and return None where there is nothing to read (a parent without the
model, a run whose gate kept the XLA path)."""
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import latent_counts
from benchmark.lib.trace import Reduced
from benchmark.readers import hybrid, latent
from benchmark.runners.serve_mla import model_config, sizes_of

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "kanana2_30b_a3b_serve.longdoc_closed128"
PEAK = 819e9
NEW = ("paged_attn_latent_roofline", "latent_attn_device_share",
       "paged_bytes_per_token")
SHARED = ("batch_occupancy", "out_tok_s_slice_p50", "peak_hbm_gib.serve",
          "decode_device_ms.tput", "prefill_device_ms_ktok.tput",
          "decode_step_p50_ms.tput", "step_host_share.tput",
          "device_idle_share.tput", "gate_keys_pallas.tput",
          "sampler_device_share", "moe_device_share", "moe_expert_roofline",
          "expert_load_max_over_mean")


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kanana2_30b_a3b_serve.json")) as f:
        cfg = json.load(f)
    cfg["sizes"] = sizes_of(cfg)
    return cfg


def _spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_counts_against_hand_figures():
    s = _config()["sizes"]
    # a token's row in a layer: 512 + 64 numbers in bf16
    assert latent_counts.latent_width(s) == 576
    assert latent_counts.latent_row_bytes(s) == 1152
    assert latent_counts.latent_bytes_a_token(s) == 7 * 1152 == 8064
    # what the keys and values it stands for would take: 32 x (192 + 128)
    assert latent_counts.expanded_bytes_a_token(s) == 7 * 20480 == 143360
    # 321,000 live tokens: 2.59 GB a decode step, 3.2 ms at the peak
    step = latent_counts.paged_latent_bytes(321_000, s)
    assert round(step / 1e9, 2) == 2.59
    assert round(step / PEAK * 1e3, 1) == 3.2
    assert latent_counts.latent_attn_flops(1, s) == 7 * 2 * 32 * (576 + 512)
    # ISSUE 32's arithmetic: attention 26,345,472 + 4,608 of norms
    assert latent_counts.attention_params(s) == 26_345_472 + 4_608
    assert latent_counts.layer_params(s, 0) == 64_098_816
    assert latent_counts.layer_params(s, 1) == 640_029_312
    assert latent_counts.weight_params(s) == 4_429_613_312
    assert round(latent_counts.weight_bytes(s) / 1e9, 2) == 8.86
    # the whole model, had it 48 layers
    whole = dict(s, num_hidden_layers=48)
    assert round(latent_counts.weight_params(whole) / 1e9, 1) == 30.7


def test_the_programs_weights_are_the_counted_ones():
    """`weight_params` counts the tree the program builds."""
    import jax
    from paddle_tpu.models import deepseek_v3 as ds
    cfg = model_config(_config())
    shapes = [ds.layer_shapes(cfg, l) for l in range(cfg.num_hidden_layers)]
    n = sum(int(jax.numpy.prod(jax.numpy.asarray(sh)))
            for sh in jax.tree_util.tree_leaves(
                shapes, is_leaf=lambda x: isinstance(x, tuple)))
    n += 2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size
    assert n == latent_counts.weight_params(_config()["sizes"])


KERNEL = ("%decode.{i} = bf16[64,32,512]{{2,1,0:T(8,128)(2,1)}} custom-call("
          "s32[64,160]{{1,0}} %pt, s32[64]{{0}} %ctx, s32[1]{{0}} %l, "
          "bf16[64,32,640]{{2,1,0}} %q, bf16[7,6401,64,640]{{3,2,1,0}} %pool)")


def _made():
    """Two decode programs of 100 us and a prefill between them. In each
    decode: the latent kernel twice (20 + 20 us), a grouped product over
    the experts' weights (30 us), the sort of the logits (10 us), a norm (5
    us). The prefill holds a custom call of another shape (the flash
    kernel, 300 us), which no reader may count."""
    dec = lambda t: [
        [KERNEL.format(i=3), t + 1_000, 20_000],
        [KERNEL.format(i=4), t + 21_000, 20_000],
        ["%gmm.7 = bf16[384,768]{1,0} custom-call(bf16[384,2048] %xs, "
         "bf16[128,2048,768]{2,1,0} %params__layers___1___ffn____w1__)",
         t + 41_000, 30_000],
        ["%sort.11 = (f32[64,128256]{1,0}, s32[64,128256]{1,0}) sort("
         "f32[64,128256] %x)", t + 71_000, 10_000],
        ["%fusion.2 = f32[64]{0} fusion(bf16[1,64,2048] %x)", t + 81_000,
         5_000]]
    ops = dec(0) + [["%flash.1 = bf16[32,8192,128]{2,1,0} custom-call("
                     "bf16[32,8192,192] %q)", 100_000, 300_000]] \
        + dec(400_000)
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [
            ["jit_decode(1)", 0, 100_000], ["jit_prefill(2)", 100_000, 300_000],
            ["jit_decode(1)", 400_000, 100_000]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ["bench.step", 0, 400_000], ["bench.step", 400_000, 110_000]]}]}
    return {"planes": [dev, host]}


def _run(trace=None, ctx=None, gauge=None):
    run = {"config": _config(), "traffic": {},
           "device_kind": "TPU v5 lite", "trace_span": (0.0, 1.0)}
    if trace is not None:
        run["trace"] = Reduced(trace)
    if ctx is not None:
        # (t0, t1, first tokens, decoded, context read, prompt tokens)
        run["loop"] = types.SimpleNamespace(
            steps=[(0.1, 0.2, 1, 63, ctx, 4000), (0.3, 0.4, 0, 64, ctx, 0),
                   (2.0, 2.1, 0, 64, 10**9, 0)])     # the last: not traced
    if gauge is not None:
        run["paged_bytes_per_token"] = gauge
    return run


def test_known_answers_on_a_made_trace():
    run = _run(_made(), ctx=300_000, gauge=9032.0)
    busy = 2 * (20 + 20 + 30 + 10 + 5)
    share = hybrid.decode_op_share(
        run, **_spec("latent_attn_device_share")["args"])
    assert share == pytest.approx(100 * 80 / busy)
    # 2 steps x 300,000 live tokens x 8,064 B in 80 us: over the roofline
    # on purpose, the reader does not clip (the harness fails such a run)
    roof = latent.paged_attn_latent_roofline(
        run, **_spec("paged_attn_latent_roofline")["args"])
    assert roof == pytest.approx(100 * 600_000 * 8064 / PEAK / 80e-6)
    assert latent.paged_bytes_per_token(run) == 9032.0
    # the hybrid readers fill their patterns from this cell's own sizes:
    # the experts' [128,2048,768], the sampler's [64,128256]
    moe = hybrid.decode_op_share(run, **_spec("moe_device_share")["args"])
    assert moe == pytest.approx(100 * 60 / busy)
    sampler = hybrid.decode_op_share(
        run, **_spec("sampler_device_share")["args"])
    assert sampler == pytest.approx(100 * 20 / busy)


def test_nothing_to_read_gives_none():
    bare = _run()
    assert latent.paged_attn_latent_roofline(bare, ops=["x"]) is None
    assert latent.paged_bytes_per_token(bare) is None
    # a trace whose decode programs hold no such operation (the gate kept
    # the XLA path)
    run = _run(_made(), ctx=3_000)
    share = _spec("latent_attn_device_share")
    assert hybrid.decode_op_share(run, ops=["no such op"]) is None
    assert latent.paged_attn_latent_roofline(run, ops=["no such"]) is None
    assert share["reader"] == "hybrid:decode_op_share"


def test_the_new_metrics_are_declared_with_their_files_and_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        spec, m = _spec(name), declared[name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert (spec["unit"], spec["layer"], spec["source"]) == \
            (m["unit"], m["layer"], m["source"])
        assert m["source"] != "program_span"
        assert "catches" in spec
        mod, fn = spec["reader"].split(":")
        assert callable(getattr({"latent": latent, "hybrid": hybrid}[mod],
                                fn))
    for name in NEW[:2]:
        assert _spec(name)["max"] == 100
    for name in SHARED:
        assert CELL in declared[name]["workloads"], name
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longdoc_closed128"
    assert cell["config"] == "kanana2_30b_a3b_serve"
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kanana2_30b_a3b_serve")
    assert entry["reduced"] == ["num_hidden_layers"]
    out = next(m for m in bench["end_to_end"] if m["name"] == "out_tok_s")
    assert CELL in out["workloads"]
    itl = next(m for m in bench["end_to_end"] if m["name"] == "itl_p95_ms")
    assert CELL not in itl["workloads"]
    assert sum(m["source"] == "program_span"
               for m in bench["per_layer"]) == 13


def test_the_configuration_holds_every_published_key_of_the_catalog():
    """The catalog's `config` of kanana-2-30b-a3b-instruct-2601, key for
    key (copied here: the catalog is not in the repo), depth alone cut,
    each reading of modeling_deepseek_v3.py under `assumed`."""
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "kv_lora_rank": 512,
        "max_position_embeddings": 32768, "model_type": "deepseek_v3",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 128, "n_shared_experts": 2,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_key_value_heads": 32,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 1000000, "routed_scaling_factor": 2.448,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 128256}
    cfg = _config()
    for key, want in published.items():
        assert cfg[key] == want, key
    assert cfg["num_hidden_layers"] == 7
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert {"n_group", "rope_interleave", "shared_experts", "router_dtype",
            "norm_topk_epsilon", "initializer_range"} <= set(cfg["assumed"])
    assert cfg["engine"] == {"num_slots": 64, "num_pages": 6400,
                             "page_size": 64, "max_seq_len": 10240,
                             "max_queue": 256}
    assert cfg["correct"]["reference_length"] == 10240
    assert cfg["correct"]["sample_requests"] == 4
    with pytest.raises(ValueError, match="q_lora_rank"):
        sizes_of({**cfg, "q_lora_rank": 1536})
    # the program's config takes the file's numbers
    mc = model_config(cfg)
    assert (mc.latent_width, mc.num_moe_layers, mc.num_experts) == \
        (576, 6, 128)


def test_the_traffic_is_the_issues_letter_for_letter():
    from benchmark.lib import traffic as traffic_lib
    tr = traffic_lib.load(traffic_lib.find(
        os.path.join(ROOT, "benchmark"), "traffic", "longdoc_closed128"))
    want = {"loop": "closed", "clients": 128, "primers": 64, "ramp_s": 0.0,
            "epoch": 128, "pairing_key": 3201,
            "prompt": {"dist": "lognormal", "median": 4096, "sigma": 0.7,
                       "min": 1024, "max": 8192},
            "output": {"dist": "lognormal", "median": 512, "sigma": 0.6,
                       "min": 128, "max": 2048},
            "sampling": {"greedy_every": 2, "temperature": 0.8,
                         "top_p": 0.9, "top_k": 0}}
    for key, value in want.items():
        assert tr[key] == value, key
    items = traffic_lib.epoch(tr)
    pages = [-(-(i["prompt_len"] + i["max_new"]) // 64) for i in items]
    # 80.9 pages a request; the longest fits a slot's 160
    assert round(sum(pages) / len(pages), 1) == 80.9 and max(pages) <= 160


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearse.py"),
         "--workload", CELL, "--trace", "1", "--seconds", "3"],
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0
    for name in ("paged_bytes_per_token", "batch_occupancy",
                 "expert_load_max_over_mean"):
        assert name in line["would_report"], name


def test_known_answers_on_decode_programs_recorded_on_the_chip():
    """tests/data/kanana_two_steps.json.gz: a prefill of the 8,192 bucket
    and the decode programs after it, cut from the cell's trace on the chip
    (scripts/pr32_cut_trace.py), and what the readers and the files'
    patterns made of it when it was recorded. The readers see the two
    decode programs that lie whole in the cut: 7 kernel calls each; the
    prefill's flash kernel and a third decode's operations are in the
    trace and in no reading."""
    import gzip
    with gzip.open(os.path.join(HERE, "data", "kanana_two_steps.json.gz")) as f:
        rec = json.loads(f.read())
    want = rec["expect"]
    run = _run(rec["trace"])
    run["loop"] = types.SimpleNamespace(
        steps=[(0.1 * i, 0.1 * i + 0.05, 0, 64, want["ctx_tokens_a_step"], 0)
               for i in range(want["decode_programs"])])
    t = run["trace"]
    assert t.window_s == pytest.approx(want["window_s"])
    assert t.busy_s == pytest.approx(want["busy_s"])
    assert t.op_count(want["kernel"]) == want["kernel_calls"] == 21
    own, busy = hybrid._decode_ops(run)
    import re
    assert sum(1 for n, _s in own if re.search(want["kernel"], n)) == 14
    assert t.op_count(r"custom-call\(.*bf16\[32,8192,192\]") == 7  # prefill
    for name, fn in (("latent_attn_device_share", hybrid.decode_op_share),
                     ("paged_attn_latent_roofline",
                      latent.paged_attn_latent_roofline),
                     ("moe_device_share", hybrid.decode_op_share),
                     ("sampler_device_share", hybrid.decode_op_share)):
        got = fn(run, **_spec(name)["args"])
        assert got == pytest.approx(want[name]), name
        assert 0 < got < 100
    # the three together are nine tenths of a decode program
    assert 85 < sum(want[n] for n in ("latent_attn_device_share",
                                      "moe_device_share",
                                      "sampler_device_share")) < 95

"""The readers of the windowed cell (readers/window.py) and the counts
behind them (lib/window_counts.py) give known answers: hand figures at
Trinity-Mini's sizes, a hand-made trace whose answers are plain, and the
whole cell rehearsed on the CPU. They look at the decode programs only,
take a layer kind's attention by the pool it touches whichever
implementation runs, and return None where there is nothing to read (a parent without the
model)."""
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import window_counts
from benchmark.lib.trace import Reduced
from benchmark.readers import hybrid, window
from benchmark.runners.serve_window import model_config, sizes_of

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "trinity_mini_serve.shortlong_closed128"
PEAK = 819e9
NEW = ("paged_attn_window_roofline", "paged_attn_full_roofline",
       "window_attn_device_share", "full_attn_device_share",
       "kv_bytes_per_context_token")
SHARED = ("batch_occupancy", "out_tok_s_slice_p50", "peak_hbm_gib.serve",
          "decode_device_ms.tput", "prefill_device_ms_ktok.tput",
          "decode_step_p50_ms.tput", "step_host_share.tput",
          "device_idle_share.tput", "gate_keys_pallas.tput",
          "sampler_device_share", "moe_device_share", "moe_expert_roofline",
          "expert_load_max_over_mean", "prefill_time_share",
          "prefill_padding_share", "decode_ahead_share",
          "host_build_transfer_ms_p50", "wait_readback_ms_p50")


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity_mini_serve.json")) as f:
        cfg = json.load(f)
    cfg["sizes"] = sizes_of(cfg)
    return cfg


def _spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_counts_against_hand_figures():
    s = _config()["sizes"]
    assert (window_counts.layers_of(s, "sliding_attention"),
            window_counts.layers_of(s, "full_attention")) == (5, 1)
    # K and V of a token in a layer: 2 x 4 heads x 128 in bf16
    assert window_counts.kv_row_bytes(s) == 2048
    # a step of 64 slots that each attend to a whole window: 1.34 GB
    assert window_counts.window_read_bytes(64 * 2048, s) == 64 * 2048 * 10240
    assert round(window_counts.window_read_bytes(64 * 2048, s) / 1e9, 2) \
        == 1.34
    # the full layer at the mix's resident context: 0.77 GB a step
    assert round(window_counts.full_read_bytes(64 * 5893, s) / 1e9, 2) == 0.77
    assert window_counts.routing_bytes_a_token(s) == 4 * 8 * 2 == 64
    assert window_counts.page_bytes(s, 64) == {"global": 131072 + 4096,
                                               "window": 655360}
    assert window_counts.whole_cache_bytes_a_token(s) == 12288 + 64
    assert window_counts.ring_pages(s, 64) == 33
    # ISSUE 40's arithmetic
    assert window_counts.attention_params(s) == 27_263_232
    assert window_counts.layer_params(s, 0) == 65_020_160
    assert window_counts.layer_params(s, 2) == 839_131_520
    assert window_counts.weight_params(s) == 4_306_554_880
    assert round(window_counts.weight_bytes(s) / 1e9, 2) == 8.61
    whole = dict(s, num_hidden_layers=32,
                 layer_types=["sliding_attention"] * 32)
    assert round(window_counts.weight_params(whole) / 1e9, 1) == 26.1


def test_the_programs_weights_are_the_counted_ones():
    """`weight_params` counts the tree the program builds."""
    import jax
    import numpy as np
    from paddle_tpu.models import afmoe
    cfg = model_config(_config())
    shapes = [afmoe.layer_shapes(cfg, l)
              for l in range(cfg.num_hidden_layers)]
    n = sum(int(np.prod(sh)) for sh in jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
    n += 2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size
    assert n == window_counts.weight_params(_config()["sizes"])


WIN_POOL, FULL_POOL = "bf16[5,2113,64,4,128]", "bf16[1,7681,64,4,128]"
# the full layer through the Pallas kernel: the pool is an operand of the call
FULL_CALL = ("%decode.9 = bf16[64,8,4,128]{3,2,1,0:T(4,128)(2,1)} custom-call("
             "s32[64,288]{1,0} %pt, s32[64]{0} %ctx, s32[1]{0} %l, "
             "bf16[64,8,4,128]{3,2,1,0} %q, " + FULL_POOL + "{4,3,2,1,0} %k, "
             + FULL_POOL + "{4,3,2,1,0} %v)")


def _window_layer(t, i):
    """One sliding layer through the XLA path: the new rows' write (0.5
    us), the two gathers of a ring a slot (3 us each), the scores and the
    weighted sum over the gathered rows (1 and 0.5 us): 8 us."""
    g = "bf16[2112,64,4,128]{3,2,1,0}"
    return [
        [f"%fusion.9{i} = {WIN_POOL}{{4,3,2,1,0}} fusion({WIN_POOL} %p, "
         f"bf16[64,4,128] %k)", t, 500],
        [f"%fusion.1{i} = {g} fusion({WIN_POOL}{{4,3,2,1,0}} %fusion.9{i}, "
         f"s32[2112]{{0}} %t)", t + 500, 3_000],
        [f"%fusion.2{i} = {g} fusion({WIN_POOL}{{4,3,2,1,0}} %fusion.8{i}, "
         f"s32[2112]{{0}} %t)", t + 3_500, 3_000],
        [f"%fusion.3{i} = f32[64,4,8,2112]{{3,2,1,0}} fusion("
         f"bf16[64,2112,4,128]{{3,2,1,0}} %bitcast.2, pred[64,2112] %live)",
         t + 6_500, 1_000],
        [f"%fusion.4{i} = bf16[64,4,8,128]{{3,2,1,0}} fusion("
         f"bf16[64,2112,4,128]{{3,2,1,0}} %bitcast.3, f32[64,4,8,2112] %p)",
         t + 7_500, 500]]


def _made(window_call=False):
    """Two decode programs of 100 us and a prefill between them. In each
    decode: the five sliding layers' attention (8 us each: the XLA path,
    or with `window_call` a Pallas call over the ring pool, which no
    program has today), the full layer's Pallas
    call (15 us), a grouped product over the experts' weights (30 us), the
    head's product into the logits (10 us), a norm (3 us). The prefill
    writes the window pool too (300 us), which no reader may count."""
    named = ("%decode.5{i} = bf16[64,8,4,128]{{3,2,1,0}} "
             "custom-call(s32[64,33]{{1,0}} %pt, s32[64]{{0}} %ctx, "
             + WIN_POOL + "{{4,3,2,1,0}} %k, " + WIN_POOL + "{{4,3,2,1,0}} %v)")
    dec = lambda t: [
        *([[named.format(i=i), t + 1_000 + 8_000 * i, 8_000]
           for i in range(5)] if window_call else
          [e for i in range(5)
           for e in _window_layer(t + 1_000 + 8_000 * i, i)]),
        [FULL_CALL, t + 41_000, 15_000],
        ["%gmm.7 = bf16[512,1024]{1,0} custom-call(bf16[512,2048] %xs, "
         "bf16[128,2048,1024]{2,1,0} %params__layers___2___ffn____w1__)",
         t + 56_000, 30_000],
        ["%fusion.11 = f32[64,200192]{1,0} fusion(bf16[64,2048] %x, "
         "bf16[2048,200192] %params__head__)", t + 86_000, 10_000],
        ["%fusion.2 = f32[64]{0} fusion(bf16[1,64,2048] %x)", t + 96_000,
         3_000]]
    ops = dec(0) + [[f"%fusion.77 = {WIN_POOL}{{4,3,2,1,0}} fusion("
                     f"{WIN_POOL} %p, bf16[256,64,4,128] %k)", 100_000,
                     300_000]] + dec(400_000)
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [
            ["jit_decode(1)", 0, 100_000], ["jit_prefill(2)", 100_000, 300_000],
            ["jit_decode(1)", 400_000, 100_000]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ["bench.step", 0, 400_000], ["bench.step", 400_000, 110_000]]}]}
    return {"planes": [dev, host]}


def _span(name, start, end, **attrs):
    return {"name": name, "start": start, "end": end, "attrs": attrs}


def _run(trace=None, ctx=None, spans=None):
    run = {"config": _config(), "traffic": {}, "window": (0.0, 1.0),
           "device_kind": "TPU v5 lite", "trace_span": (0.0, 1.0)}
    if trace is not None:
        run["trace"] = Reduced(trace)
    if ctx is not None:
        # (t0, t1, first tokens, decoded, context read, prompt tokens)
        run["loop"] = types.SimpleNamespace(
            steps=[(0.1, 0.2, 1, 63, ctx, 4000), (0.3, 0.4, 0, 64, ctx, 0),
                   (2.0, 2.1, 0, 64, 10**9, 0)])     # the last: not traced
    # what readers/spans.py::program_spans would have read from the ring
    run["_program_spans"] = spans
    return run


SPANS = [
    _span("engine.step", 0.1, 0.2, pages_reserved=6000,
          window_pages_reserved=2000),
    _span("engine.decode", 0.15, 0.2, window_rows=100_000),
    _span("engine.step", 0.3, 0.4, pages_reserved=6400,
          window_pages_reserved=2040),
    _span("engine.decode", 0.35, 0.4, window_rows=120_000),
    _span("engine.step", 2.0, 2.1, pages_reserved=1,
          window_pages_reserved=1),
    _span("engine.decode", 2.05, 2.1, window_rows=10**9)]


@pytest.mark.parametrize("window_call", [False, True])
def test_known_answers_on_a_made_trace(window_call):
    """Whichever implementation ran the sliding layers: a reading goes by
    the pool touched."""
    run = _run(_made(window_call), ctx=380_000, spans=SPANS)
    busy = 2 * (5 * 8 + 15 + 30 + 10 + 3)
    win = window.decode_op_share(
        run, **_spec("window_attn_device_share")["args"])
    assert win == pytest.approx(100 * 2 * 40 / busy)
    full = window.decode_op_share(
        run, **_spec("full_attn_device_share")["args"])
    assert full == pytest.approx(100 * 2 * 15 / busy)
    # 220,000 window rows x 2,048 B x 5 layers in 80 us: over the roofline
    # on purpose, the reader does not clip (the harness fails such a run)
    roof = window.paged_attn_window_roofline(
        run, **_spec("paged_attn_window_roofline")["args"])
    assert roof == pytest.approx(100 * 220_000 * 10240 / PEAK / 80e-6)
    roof = window.paged_attn_full_roofline(
        run, **_spec("paged_attn_full_roofline")["args"])
    assert roof == pytest.approx(100 * 760_000 * 2048 / PEAK / 30e-6)
    # (6,200 pages x 135,168 + 2,020 ring pages x 655,360) / 380,000
    got = window.kv_bytes_per_context_token(run)
    assert got == pytest.approx((6200 * 135168 + 2020 * 655360) / 380_000)
    assert 5000 < got < 7000
    # the hybrid readers fill their patterns from this cell's own sizes:
    # the experts' [128,2048,1024], the logits' [64,200192]
    moe = hybrid.decode_op_share(run, **_spec("moe_device_share")["args"])
    assert moe == pytest.approx(100 * 60 / busy)
    sampler = hybrid.decode_op_share(
        run, **_spec("sampler_device_share")["args"])
    assert sampler == pytest.approx(100 * 20 / busy)


def test_nothing_to_read_gives_none():
    bare = _run()
    assert window.paged_attn_window_roofline(bare, ops=["x"]) is None
    assert window.paged_attn_full_roofline(bare, ops=["x"]) is None
    assert window.kv_bytes_per_context_token(bare) is None
    # a program whose spans carry no group's attributes (the parent's)
    old = [_span("engine.step", 0.1, 0.2, pages_reserved=6000),
           _span("engine.decode", 0.15, 0.2, active=64)]
    run = _run(_made(), ctx=3_000, spans=old)
    assert window.kv_bytes_per_context_token(run) is None
    assert window.paged_attn_window_roofline(
        run, **_spec("paged_attn_window_roofline")["args"]) is None
    # a trace whose decode programs hold no such operation
    run = _run(_made(), ctx=3_000, spans=SPANS)
    assert window.paged_attn_window_roofline(run, ops=["no such"]) is None
    assert window.paged_attn_full_roofline(run, ops=["no such"]) is None
    assert window.decode_op_share(run, ops=["no such op"]) is None


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
        assert callable(getattr({"window": window, "hybrid": hybrid}[mod],
                                fn))
    for name in NEW[:4]:        # shares of a peak or of a whole
        assert _spec(name)["max"] == 100
    for name in SHARED:
        assert CELL in declared[name]["workloads"], name
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(NEW)
    cell = bench["workloads"][-1]
    assert cell == {"name": CELL, "config": "trinity_mini_serve",
                    "traffic": "shortlong_closed128", "chips": 1,
                    "why": cell["why"]}
    entry = bench["configs"][-1]
    assert entry["name"] == "trinity_mini_serve"
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    out = next(m for m in bench["end_to_end"] if m["name"] == "out_tok_s")
    assert out["workloads"][-1] == CELL
    itl = next(m for m in bench["end_to_end"] if m["name"] == "itl_p95_ms")
    assert CELL not in itl["workloads"]


def test_the_configuration_holds_every_published_key_of_the_catalog():
    """The catalog's `config` of Trinity-Mini, key for key (copied here:
    the catalog is not in the repo), depth alone cut, each reading of
    modeling_afmoe.py under `assumed`."""
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_expert_groups": 1, "num_experts": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    cfg = _config()
    for key, want in published.items():
        assert cfg[key] == want, key
    assert cfg["num_hidden_layers"] == 6
    assert cfg["layer_types"] == ["sliding_attention"] * 3 \
        + ["full_attention"] + ["sliding_attention"] * 2
    assert cfg["published"] == {"num_hidden_layers": 32}
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]
    assert {"mup_enabled", "gate_proj", "qk_norm", "rope", "window",
            "four_norms", "router", "norm_topk_epsilon", "shared_experts",
            "initializer_range"} <= set(cfg["assumed"])
    assert cfg["engine"] == {"num_slots": 64, "num_pages": 7680,
                             "page_size": 64, "max_seq_len": 18432,
                             "max_queue": 256}
    # a ring of 33 pages a slot: the window pool of the patterns
    assert window._fields({"config": cfg, "traffic": {}})[
        "window_pool_rows"] == 64 * 33 + 1
    assert cfg["correct"]["reference_length"] == 18432
    assert cfg["correct"]["sample_requests"] == 4
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        sizes_of({**cfg, "tie_word_embeddings": True})
    mc = model_config(cfg)
    assert (mc.num_moe_layers, mc.num_experts, mc.sliding_window) == \
        (4, 128, 2048)
    assert mc.layer_types == tuple(cfg["layer_types"])


def test_the_traffic_is_the_issues_letter_for_letter():
    from benchmark.lib import traffic as traffic_lib
    tr = traffic_lib.load(traffic_lib.find(
        os.path.join(ROOT, "benchmark"), "traffic", "shortlong_closed128"))
    want = {"loop": "closed", "clients": 128, "primers": 64, "ramp_s": 0.0,
            "epoch": 128, "order": "file",
            "prompt": {"dist": "lognormal", "median": 4096, "sigma": 1.0,
                       "min": 256, "max": 16384},
            "output": {"dist": "lognormal", "median": 384, "sigma": 0.6,
                       "min": 96, "max": 1536},
            "sampling": {"greedy_every": 2, "temperature": 0.8,
                         "top_p": 0.9, "top_k": 0}}
    for key, value in want.items():
        assert tr[key] == value, key
    items = traffic_lib.epoch(tr)
    prompts = [i["prompt_len"] for i in items]
    # a quarter of the prompts inside the window, a quarter of 8k and more
    assert sum(p <= 2048 for p in prompts) == 31
    assert sum(p >= 8192 for p in prompts) == 31
    assert (min(prompts), max(prompts)) == (286, 16384)
    assert round(sum(prompts) / 128) == 5749
    pages = [-(-(i["prompt_len"] + i["max_new"]) // 64) for i in items]
    # 97.5 pages a request; the longest fits a slot's 288
    assert round(sum(pages) / len(pages), 1) == 97.5 and max(pages) <= 288


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearse.py"),
         "--workload", CELL, "--trace", "1", "--seconds", "3"],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0
    for name in ("kv_bytes_per_context_token", "batch_occupancy",
                 "expert_load_max_over_mean", "prefill_time_share"):
        assert name in line["would_report"], name


def test_known_answers_on_decode_programs_recorded_on_the_chip():
    """tests/data/trinity_two_steps.json.gz: a prefill and the two decode
    programs after it, cut from the cell's trace on the chip (PR 40's final
    tree, the second: a ring a slot; scripts/pr32_cut_trace.py with names of 900 characters: the pool
    is the call's sixth operand). The sliding layers run the XLA gather
    (its two gathers of a ring a slot, the products over `[64,2112,4,128]`)
    and the full layer the Pallas kernel: the patterns take both by the
    pool they touch. The prefill copies both pools whole (PERF.md,
    section 7) and is in no reading."""
    import gzip
    with gzip.open(os.path.join(HERE, "data", "trinity_two_steps.json.gz")) as f:
        rec = json.loads(f.read())
    run = _run(rec["trace"], spans=[
        _span("engine.decode", 0.1, 0.2, window_rows=121_000),
        _span("engine.decode", 0.3, 0.4, window_rows=121_000)])
    run["loop"] = types.SimpleNamespace(
        steps=[(0.1 * i, 0.1 * i + 0.05, 0, 64, 377_000, 0) for i in (1, 3)])
    own, busy = hybrid._decode_ops(run)
    assert busy == pytest.approx(0.05948287)        # two programs of 29.7 ms
    import re
    assert sum(1 for n, _s in own if re.search(
        r"^%decode[\w.]* = bf16\[64,8,4,128\].*custom-call\(.*"
        r"bf16\[1,7681,64,4,128\]", n)) == 2        # the full layer's call
    want = {"window_attn_device_share": 23.5178, "full_attn_device_share":
            37.2158}
    for name, value in want.items():
        got = window.decode_op_share(run, **_spec(name)["args"])
        assert got == pytest.approx(value, abs=1e-3), name
    # 2 x 121,000 window rows x 10,240 B in 13.99 ms; 2 x 377,000 context
    # tokens x 2,048 B in 22.14 ms
    got = window.paged_attn_window_roofline(
        run, **_spec("paged_attn_window_roofline")["args"])
    assert got == pytest.approx(100 * 242_000 * 10240 / PEAK / 0.013989065)
    assert 20 < got < 25
    got = window.paged_attn_full_roofline(
        run, **_spec("paged_attn_full_roofline")["args"])
    assert got == pytest.approx(100 * 754_000 * 2048 / PEAK / 0.022137051)
    assert 8 < got < 9
    # the shared readers on the same programs: experts, head and sampler
    assert hybrid.decode_op_share(
        run, **_spec("moe_device_share")["args"]) == pytest.approx(27.41, abs=0.01)
    assert hybrid.decode_op_share(
        run, **_spec("sampler_device_share")["args"]) == pytest.approx(12.01, abs=0.01)

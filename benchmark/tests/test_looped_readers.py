"""The readers of the looped cell (readers/looped.py) and the byte counts
behind them (lib/looped_counts.py) give known answers: hand figures at
Ouro-2.6B's sizes, a hand-made trace whose answers are plain, and the whole
cell rehearsed on the CPU. They look at the decode programs only, count a
`while`'s body once, and return None where there is nothing to read."""
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.lib import looped_counts
from benchmark.lib.trace import Reduced
from benchmark.readers import looped
from benchmark.runners.serve_looped import sizes_of

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "ouro_2p6b_serve.decode_closed32"
PEAK = 819e9
NEW = ("loop_passes_per_token", "paged_attn_looped_roofline",
       "attn_device_share.tput", "decode_weight_roofline")


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro_2p6b_serve.json")) as f:
        cfg = json.load(f)
    cfg["sizes"] = sizes_of(cfg)
    return cfg


def _spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_byte_counts_against_hand_figures():
    s = _config()["sizes"]
    assert looped_counts.cache_rows(s) == 192
    # K and V, 4 x 48 rows of 16 heads of 128, bf16: 1.5 MiB a token
    assert looped_counts.kv_bytes_a_token(s) == 1.5 * 2 ** 20
    assert looped_counts.paged_kv_bytes(3600, s) == 3600 * 1572864
    # a layer: 4 x 2048^2 + 3 x 2048 x 5632 + 4 x 2048 parameters
    assert looped_counts.layer_weight_bytes(s) == 2 * 51_388_416
    assert looped_counts.head_weight_bytes(s) == 2 * 2048 * 49152
    # a decode step: the layers four times and the head: 19.9 GB
    step = looped_counts.decode_weight_bytes(s)
    assert step == 4 * 48 * 2 * 51_388_416 + 2 * 2048 * 49152
    assert round(step / 1e9, 1) == 19.9
    assert round(step / PEAK * 1e3, 1) == 24.3          # ms at the peak


def _made():
    """Two decode programs of 100 us and a prefill between them. In each
    decode a `while` of 90 us holds: two fusions that slice a layer's
    matrix out of the stack and multiply (30 + 20 us), the paged kernel
    (25 us), a norm (5 us); then the head (8 us). The prefill holds a
    weight fusion too (300 us), which no reader may count."""
    w1 = "bf16[48,2048,5632]{2,1,0} %get-tuple-element.1"
    wq = "bf16[48,2048,2048]{2,1,0} %get-tuple-element.2"
    loop = ("%while.1 = (s32[], bf16[1,16,2048], bf16[192,385,16,16,128], "
            "bf16[48,2048,5632], bf16[48,2048,2048]) while(%tuple.1)")
    dec = lambda t: [
        [loop, t, 90_000],
        [f"%fusion.7 = bf16[16,5632]{{1,0}} fusion({w1}, s32[] %i)", t + 1_000,
         30_000],
        [f"%fusion.9 = bf16[16,2048]{{1,0}} fusion({wq}, s32[] %i)",
         t + 31_000, 20_000],
        ["%decode.3 = bf16[16,16,128]{2,1,0} custom-call(s32[16,64] %pt)",
         t + 51_000, 25_000],
        ["%fusion.2 = f32[16]{0} fusion(bf16[1,16,2048] %x)", t + 76_000,
         5_000],
        ["%fusion.30 = f32[16,49152]{1,0} fusion(bf16[2048,49152]{1,0} "
         "%params__head__.1)", t + 90_000, 8_000]]
    ops = dec(0) + [[f"%fusion.70 = bf16[256,5632] fusion({w1}, s32[] %i)",
                     100_000, 300_000]] + dec(400_000)
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [
            ["jit_decode(1)", 0, 100_000], ["jit_prefill(2)", 100_000, 300_000],
            ["jit_decode(1)", 400_000, 100_000]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ["bench.step", 0, 400_000], ["bench.step", 400_000, 110_000]]}]}
    return {"planes": [dev, host]}


def _run(trace=None, ctx=None, passes=None):
    run = {"config": _config(), "traffic": {}, "device_kind": "TPU v5 lite",
           "trace_span": (0.0, 1.0)}
    if trace is not None:
        run["trace"] = Reduced(trace)
    if ctx is not None:
        # (t0, t1, first tokens, decoded, context read, prompt tokens)
        run["loop"] = types.SimpleNamespace(
            steps=[(0.1, 0.2, 1, 15, ctx, 100), (0.3, 0.4, 0, 16, ctx, 0),
                   (2.0, 2.1, 0, 16, 10**9, 0)])     # the last: not traced
    if passes is not None:
        run["stats_log"] = [
            {"at": "", "loop_passes": [7] * 4},
            {"at": "trace_start", "loop_passes": [50] * 4},
            {"at": "", "loop_passes": [7 + 147] * passes
             + [7] * (4 - passes)}]
    return run


def test_known_answers_on_a_made_trace():
    run = _run(_made(), ctx=3_000, passes=4)
    s = run["config"]["sizes"]
    busy = 2 * (1 + 30 + 20 + 25 + 5 + 9 + 8)    # the while's own: 1 + 9 us
    share = looped.decode_op_share(
        run, **_spec("attn_device_share.tput")["args"])
    assert share == pytest.approx(100 * 50 / busy)
    # 2 steps x 3,000 live tokens x 1.5 MiB in 50 us
    roof = looped.paged_attn_looped_roofline(
        run, **_spec("paged_attn_looped_roofline")["args"])
    assert roof == pytest.approx(
        100 * 6_000 * 1.5 * 2 ** 20 / PEAK / 50e-6)
    # 2 programs x 19.9 GB in 2 x (30 + 20 + 8) us: the while is not a read
    w = looped.decode_weight_roofline(
        run, **_spec("decode_weight_roofline")["args"])
    assert w == pytest.approx(
        100 * 2 * looped_counts.decode_weight_bytes(s) / PEAK / 116e-6)
    # 147 tokens went in (100 prompt + 15 + 16 + 16 decoded), each 4 times
    assert looped.loop_passes_per_token(run) == 4.0
    assert looped.loop_passes_per_token(
        _run(_made(), ctx=3_000, passes=3)) == 3.0


def test_nothing_to_read_gives_none():
    bare = _run()
    assert looped.decode_op_share(bare, ops=["x"]) is None
    assert looped.paged_attn_looped_roofline(bare, ops=["x"]) is None
    assert looped.decode_weight_roofline(bare, ops=["x"]) is None
    assert looped.loop_passes_per_token(bare) is None
    # a trace and a loop, but a program without the tally (the parent)
    assert looped.loop_passes_per_token(_run(_made(), ctx=3_000)) is None
    # a trace whose decode programs hold no such operation
    run = _run(_made(), ctx=3_000)
    assert looped.decode_op_share(run, ops=["no such op"]) is None
    assert looped.paged_attn_looped_roofline(run, ops=["no such"]) is None
    assert looped.decode_weight_roofline(run, ops=["no such"]) is None


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
        assert mod == "looped" and callable(getattr(looped, fn))
    for name in ("paged_attn_looped_roofline", "decode_weight_roofline"):
        assert _spec(name)["max"] == 100
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "decode_closed32"
    # the cell reports the end-to-end metric its per-layer metrics move
    out = next(m for m in bench["end_to_end"] if m["name"] == "out_tok_s")
    assert CELL in out["workloads"]
    itl = next(m for m in bench["end_to_end"] if m["name"] == "itl_p95_ms")
    assert CELL not in itl["workloads"]


def test_the_configuration_holds_every_published_key_of_the_catalog():
    """The catalog's `config` of Ouro-2.6B, key for key (copied here: the
    catalog is not in the repo), `reduced` empty, each reading of
    modeling_ouro.py under `assumed`."""
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152, "layer_types": ["full_attention"] * 48}
    cfg = _config()
    for key, want in published.items():
        assert cfg[key] == want, key
    assert cfg["reduced"] == []
    assert {"norms_a_layer", "projection_biases", "norm_inside_the_loop",
            "cache_index", "exit_gate", "initialisation"} <= set(
                cfg["assumed"])
    assert cfg["engine"] == {"num_slots": 16, "num_pages": 384,
                             "page_size": 16, "max_seq_len": 1024,
                             "max_queue": 256}
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        sizes_of({**cfg, "tie_word_embeddings": True})


def test_the_cell_rehearses_on_the_cpu_and_counts_four_passes():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearse.py"),
         "--workload", CELL, "--trace", "1", "--seconds", "3"],
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0
    assert "loop_passes_per_token" in line["would_report"]
    assert "batch_occupancy" in line["would_report"]


def test_known_answers_on_a_decode_step_recorded_on_the_chip():
    """tests/data/ouro_one_step.json.gz: what the readers and the files'
    patterns made of it when it was recorded; 192 kernel calls a step."""
    import gzip
    with gzip.open(os.path.join(HERE, "data", "ouro_one_step.json.gz")) as f:
        rec = json.loads(f.read())
    want = rec["expect"]
    run = _run(rec["trace"])
    run["loop"] = types.SimpleNamespace(
        steps=[(0.1, 0.2, 0, 16, want["ctx_tokens_a_step"], 0)])
    t = run["trace"]
    assert t.window_s == pytest.approx(want["window_s"])
    assert t.busy_s == pytest.approx(want["busy_s"])
    assert t.op_count(want["kernel"]) == want["kernel_calls"] == 192
    assert t.op_seconds(want["kernel"]) == pytest.approx(want["kernel_s"])
    for name, fn in (("attn_device_share.tput", looped.decode_op_share),
                     ("paged_attn_looped_roofline",
                      looped.paged_attn_looped_roofline),
                     ("decode_weight_roofline",
                      looped.decode_weight_roofline)):
        got = fn(run, **_spec(name)["args"])
        assert got == pytest.approx(want[name]), name
        assert 0 < got < 100
    # the weights' operations and the kernel are different operations, and
    # together most of the program
    s = run["config"]["sizes"]
    weights_s = looped_counts.decode_weight_bytes(s) / PEAK \
        / (want["decode_weight_roofline"] / 100)
    assert 0.85 * t.busy_s < weights_s + want["kernel_s"] < t.busy_s

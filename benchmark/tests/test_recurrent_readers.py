"""The readers of the recurrent cell (readers/recurrent.py) and the counts
behind them (lib/recurrent_counts.py) give known answers: hand figures at
Jamba2-3B's sizes, a hand-made trace whose answers are plain, a prefill and
two decode programs recorded on the chip, and the whole cell rehearsed on
the CPU. They take a layer's operations by the shapes a trace's names
carry, look at the programs of one kind only (the state and the paged call
in `jit_decode`, the scan in `jit_prefill`), and return None where there is
nothing to read (a parent without the model)."""
import gzip
import json
import os
import re
import subprocess
import sys
import types

import pytest

from benchmark.lib import recurrent_counts
from benchmark.lib.trace import Reduced
from benchmark.readers import hybrid, recurrent
from benchmark.runners.serve_recurrent import model_config, sizes_of

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "jamba2_3b_serve.chat_closed512"
PEAK = 819e9
NEW = ("ssm_device_share", "ssm_state_roofline", "ssm_scan_roofline",
       "paged_attn_mqa_roofline", "slot_state_bytes_per_slot")
SHARED = ("batch_occupancy", "out_tok_s_slice_p50", "peak_hbm_gib.serve",
          "decode_device_ms.tput", "prefill_device_ms_ktok.tput",
          "decode_step_p50_ms.tput", "step_host_share.tput",
          "device_idle_share.tput", "gate_keys_pallas.tput",
          "sampler_device_share", "prefill_time_share",
          "prefill_padding_share", "decode_ahead_share",
          "host_build_transfer_ms_p50", "wait_readback_ms_p50")


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "jamba2_3b_serve.json")) as f:
        cfg = json.load(f)
    cfg["sizes"] = sizes_of(cfg)
    return cfg


def _spec(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def _read(run, name):
    spec = _spec(name)
    return getattr(recurrent, spec["reader"].split(":")[1])(
        run, **spec["args"])


def test_counts_against_hand_figures():
    s = _config()["sizes"]
    assert (recurrent_counts.mamba_layers(s),
            recurrent_counts.attention_layers(s)) == (26, 2)
    assert recurrent_counts.channels(s) == 5120
    # h [16, 5120] float32 and three taps [5120] in bf16
    assert recurrent_counts.state_bytes_a_layer(s) == 327_680 + 30_720
    assert recurrent_counts.state_bytes_a_slot(s) == 9_318_400
    assert recurrent_counts.state_bytes_a_slot(s, state_itemsize=2) \
        == 5_058_560
    # a step of 256 live slots reads and writes 4.77 GB of state
    assert round(recurrent_counts.step_state_bytes(256, s) / 1e9, 2) == 4.77
    # ONE KV head: 512 B a token a layer, 1 KiB over the two layers
    assert recurrent_counts.kv_row_bytes(s) == 512
    assert recurrent_counts.kv_bytes_a_token(s) == 1024
    assert recurrent_counts.mqa_read_bytes(1000, s) == 1_024_000
    # a bucket of 2,048: u, delta, y at 5,120 in bf16 and B, C at 16 in
    # float32, 26 layers: 1.64 GB; the carry 327,680 B a layer a prompt
    assert recurrent_counts.scan_stream_bytes(2048, s) \
        == 26 * 2048 * (3 * 5120 * 2 + 2 * 16 * 4)
    assert recurrent_counts.scan_carry_bytes(3, s) == 26 * 3 * 327_680


def test_the_programs_cache_holds_the_counted_bytes():
    """`state_bytes_a_slot` and `kv_bytes_a_token` count the parts the
    program builds (shapes only: nothing is allocated)."""
    import jax
    from paddle_tpu.serving import RecurrentDecodeModel
    cfg = _config()
    model = RecurrentDecodeModel.__new__(RecurrentDecodeModel)
    model.cfg = model_config(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(6144, 64, 256))
    size = lambda a: a.size * a.dtype.itemsize      # noqa: E731
    assert size(cache["ssm"]) + size(cache["conv"]) \
        == 256 * recurrent_counts.state_bytes_a_slot(cfg["sizes"])
    assert cache["ssm"].shape == (26, 256, 16, 5120)
    assert cache["conv"].shape == (26, 3, 256, 5120)
    assert cache["kv"].shape == (2, 6145, 64, 256)
    assert size(cache["kv"]) // (6145 * 64) \
        == recurrent_counts.kv_bytes_a_token(cfg["sizes"])


STATE, TAPS = "f32[26,256,16,5120]", "bf16[26,3,256,5120]"
POOL = "bf16[2,6145,64,256]"


def _made():
    """A prefill of 400 us between two decode programs of 100 us. In each
    decode: a mixer's in-projection (10 us), the state's two fusions (y,
    then the update in place: 12 + 18 us), the taps' shift (2 us), the
    out-projection (5 us), the paged call (6 us), an MLP product (20 us),
    the head's product into the logits (10 us), a norm (3 us). The prefill
    holds the scan's custom call (60 us) and an XLA-form loop's body over
    the carry (20 us), and touches neither pool; its in-projection is 2 E
    wide too and is in no DECODE reading."""
    dec = lambda t: [
        ["%fusion.1 = bf16[256,1,10240]{2,0,1} fusion(bf16[26,2560,10240] "
         "%w_in, s32[] %l, bf16[256,1,2560] %x)", t + 1_000, 10_000],
        [f"%fusion.2 = f32[256,5120]{{1,0}} fusion({STATE} %ssm, s32[] %l, "
         "f32[256,16] %c)", t + 11_000, 12_000],
        [f"%add_dynamic-update-slice_fusion.3 = {STATE}{{3,2,1,0}} fusion("
         f"{STATE} %ssm, s32[] %l, f32[256,16] %b)", t + 23_000, 18_000],
        [f"%fusion.4 = {TAPS}{{3,2,1,0}} fusion({TAPS} %conv, s32[] %l, "
         "bf16[1,256,5120] %u)", t + 41_000, 2_000],
        ["%fusion.5 = bf16[256,1,2560]{2,0,1} fusion(bf16[26,5120,2560] "
         "%w_out, bf16[1,256,5120] %y)", t + 43_000, 5_000],
        ["%decode.6 = bf16[256,20,128]{2,1,0} custom-call(s32[256,64] %pt, "
         "s32[256] %ctx, s32[1] %l, bf16[256,20,256] %q, " + POOL + " %kv)",
         t + 48_000, 6_000],
        ["%fusion.7 = bf16[256,8192]{1,0} fusion(bf16[28,2560,8192] %w1, "
         "bf16[256,1,2560] %x)", t + 54_000, 20_000],
        ["%fusion.8 = f32[256,65536]{1,0} fusion(bf16[65536,2560] "
         "%params__embed__.1, bf16[256,1,2560] %x)", t + 74_000, 10_000],
        ["%fusion.9 = f32[256]{0} fusion(bf16[256,1,2560] %x)", t + 84_000,
         3_000]]
    pre = [
        ["%fusion.21 = bf16[1,1024,10240]{2,1,0} fusion(bf16[26,2560,10240] "
         "%w_in, bf16[1,1024,2560] %x)", 101_000, 200_000],
        ["%selective_scan.22 = (f32[1,1024,5,8,128]{4,3,2,1,0}, "
         "f32[1,16,5,8,128]{4,3,2,1,0}) custom-call(f32[16384] %b, "
         "f32[16384] %c, f32[1,1024,5,8,128] %u)", 301_000, 60_000],
        ["%fusion.23 = (f32[1,16,5120]{2,1,0}, f32[1,5120]) fusion("
         "f32[1,16,5120] %h, f32[1,5120] %dt)", 361_000, 20_000]]
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": dec(0) + pre + dec(500_000)},
        {"name": "XLA Modules", "events": [
            ["jit_decode(1)", 0, 100_000],
            ["jit_prefill(2)", 100_000, 400_000],
            ["jit_decode(1)", 500_000, 100_000]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ["bench.step", 0, 500_000], ["bench.step", 500_000, 110_000]]}]}
    return {"planes": [dev, host]}


def _span(name, start, end, **attrs):
    return {"name": name, "start": start, "end": end, "attrs": attrs}


def _run(trace=None, ctx=None, spans=None):
    run = {"config": _config(), "traffic": {}, "window": (0.0, 1.0),
           "device_kind": "TPU v5 lite", "trace_span": (0.0, 1.0),
           "slots": 256}
    if trace is not None:
        run["trace"] = Reduced(trace)
    if ctx is not None:
        # (t0, t1, first tokens, decoded, context read, prompt tokens)
        run["loop"] = types.SimpleNamespace(
            steps=[(0.1, 0.2, 1, 255, ctx, 700), (0.3, 0.4, 0, 256, ctx, 0),
                   (2.0, 2.1, 0, 256, 10**9, 0)])    # the last: not traced
    # what readers/spans.py::program_spans would have read from the ring
    run["_program_spans"] = spans
    return run


SPANS = [
    _span("engine.prefill", 0.05, 0.09, scan_len=1024, scan_chunks=4),
    _span("engine.decode", 0.15, 0.2, state_rows=250),
    _span("engine.decode", 0.35, 0.4, state_rows=256),
    _span("engine.prefill", 2.0, 2.05, scan_len=10**6, scan_chunks=1),
    _span("engine.decode", 2.05, 2.1, state_rows=10**6)]


def test_known_answers_on_a_made_trace():
    run = _run(_made(), ctx=150_000, spans=SPANS)
    busy = 2 * (10 + 12 + 18 + 2 + 5 + 6 + 20 + 10 + 3)
    # every operation that names an array E or 2 E wide: the projections,
    # both state fusions, the taps; not the MLP, the call or the head
    assert _read(run, "ssm_device_share") == pytest.approx(
        100 * 2 * (10 + 12 + 18 + 2 + 5) / busy)
    # 506 live rows x 2 x 9,318,400 B in 2 x 32 us: over the roofline on
    # purpose, the reader does not clip (the harness fails such a run)
    assert _read(run, "ssm_state_roofline") == pytest.approx(
        100 * 506 * 2 * 9_318_400 / PEAK / 64e-6)
    # one bucket of 1,024 and its carry, in the kernel's 60 us and the XLA
    # loop's 20: the in-projection of the prefill is not the scan
    assert _read(run, "ssm_scan_roofline") == pytest.approx(
        100 * 26 * (1024 * (3 * 5120 * 2 + 128) + 327_680) / PEAK / 80e-6)
    # 300,000 context tokens x 1,024 B in 12 us
    assert _read(run, "paged_attn_mqa_roofline") == pytest.approx(
        100 * 300_000 * 1024 / PEAK / 12e-6)
    run["slot_state_bytes"] = 256 * 9_318_400.0
    assert _read(run, "slot_state_bytes_per_slot") == 9_318_400
    # the shared reader fills its pattern from this cell's own sizes
    assert hybrid.decode_op_share(
        run, **_spec("sampler_device_share")["args"]) is None   # tied head
    run2 = _run(_made(), ctx=150_000, spans=SPANS)
    for ev in run2["trace"].devices[0]["ops"]:
        ev[0] = ev[0].replace("params__embed__.1", "logits")
    assert hybrid.decode_op_share(
        run2, **_spec("sampler_device_share")["args"]) == pytest.approx(
            100 * 20 / busy)


def test_nothing_to_read_gives_none():
    bare = _run()
    for name in NEW[:4]:
        assert _read(bare, name) is None
    assert _read(bare, "slot_state_bytes_per_slot") is None
    # a program whose spans carry none of the attributes (the parent's)
    old = [_span("engine.prefill", 0.05, 0.09, bucket=1024),
           _span("engine.decode", 0.15, 0.2, active=250)]
    run = _run(_made(), ctx=3_000, spans=old)
    assert _read(run, "ssm_state_roofline") is None
    assert _read(run, "ssm_scan_roofline") is None
    # a trace whose programs hold no such operation
    run = _run(_made(), ctx=3_000, spans=SPANS)
    assert recurrent.ssm_device_share(run, ops=["no such op"]) is None
    assert recurrent.ssm_state_roofline(run, ops=["no such"]) is None
    assert recurrent.ssm_scan_roofline(run, ops=["no such"]) is None
    assert recurrent.paged_attn_mqa_roofline(run, ops=["no such"]) is None
    # no steps of the harness's loop: no context to count
    del run["loop"]
    assert _read(run, "paged_attn_mqa_roofline") is None


def test_the_new_metrics_are_declared_with_their_files_and_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        spec, m = _spec(name), declared[name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert (spec["unit"], spec["layer"], spec["source"]) == \
            (m["unit"], m["layer"], m["source"])
        assert "catches" in spec and spec["name"] == name
        mod, fn = spec["reader"].split(":")
        assert mod == "recurrent" and callable(getattr(recurrent, fn))
    for name in NEW[:4]:        # shares of a peak or of a whole
        assert _spec(name)["max"] == 100
    assert "max" not in _spec("slot_state_bytes_per_slot")
    for name in NEW[1:4]:       # the rooflines come from the device trace
        assert declared[name]["source"] == "device_trace"
        assert name.endswith("_roofline") and declared[name]["unit"] == "%"
    for name in SHARED:
        assert CELL in declared[name]["workloads"], name
    # (no pin on where in their lists: the next configuration's entries
    # come after these, as these came after the windowed cell's)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "jamba2_3b_serve",
                    "traffic": "chat_closed512", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"]
                 if c["name"] == "jamba2_3b_serve")
    assert entry["reduced"] == []
    out = next(m for m in bench["end_to_end"] if m["name"] == "out_tok_s")
    assert CELL in out["workloads"]
    itl = next(m for m in bench["end_to_end"] if m["name"] == "itl_p95_ms")
    assert CELL not in itl["workloads"]


def test_the_configuration_holds_every_published_key_of_the_catalog():
    """The catalog's `config` of AI21-Jamba2-3B, key for key (copied here:
    the catalog is not in the repo), nothing cut, each reading of
    modeling_jamba.py under `assumed`."""
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 8192, "mamba_conv_bias": True,
        "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
        "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    cfg = _config()
    for key, want in published.items():
        assert cfg[key] == want, key
    assert cfg["reduced"] == []
    assert {"layer_order", "experts", "inner_norms", "positions", "head_dim",
            "biases", "ssm_state_dtype", "initialisation"} \
        <= set(cfg["assumed"])
    assert cfg["assumed"]["ssm_state_dtype"].startswith("float32")
    assert cfg["engine"] == {"num_slots": 256, "num_pages": 6144,
                             "page_size": 64, "max_seq_len": 4096,
                             "max_queue": 1024}
    assert cfg["correct"]["reference_length"] == 4096
    with pytest.raises(ValueError, match="num_experts"):
        sizes_of({**cfg, "num_experts": 16})
    mc = model_config(cfg)
    assert [l for l, k in enumerate(mc.layer_types) if k == "attention"] \
        == [7, 21]
    assert cfg["sizes"]["layer_types"] == list(mc.layer_types)
    assert (mc.d_inner, mc.head_dim, mc.dtype) == (5120, 128, "bfloat16")
    # the sizes the shapes of the patterns are filled from
    f = recurrent._fields({"config": cfg})
    assert (f["channels"], f["in_width"], f["xproj_width"], f["taps"],
            f["kv_width"], f["pool_rows"], f["table_positions"]) \
        == (5120, 10240, 192, 3, 256, 6145, 4096)


def test_the_traffic_is_the_issues_letter_for_letter():
    from benchmark.lib import traffic as traffic_lib
    tr = traffic_lib.load(traffic_lib.find(
        os.path.join(ROOT, "benchmark"), "traffic", "chat_closed512"))
    want = {"loop": "closed", "clients": 512, "primers": 256, "ramp_s": 0.0,
            "epoch": 512, "order": "file",
            "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.9,
                       "min": 32, "max": 2048},
            "output": {"dist": "lognormal", "median": 384, "sigma": 0.6,
                       "min": 64, "max": 1536},
            "sampling": {"greedy_every": 2, "temperature": 0.8,
                         "top_p": 0.9, "top_k": 0}}
    for key, value in want.items():
        assert tr[key] == value, key
    assert 4001 <= tr["pairing_key"] <= 4099
    items = traffic_lib.epoch(tr)
    prompts = [i["prompt_len"] for i in items]
    outputs = [i["max_new"] for i in items]
    assert (min(prompts), max(prompts)) == (32, 2048)
    assert round(sum(prompts) / 512) == 375
    assert round(sum(outputs) / 512) == 456
    pages = [-(-(i["prompt_len"] + i["max_new"]) // 64) for i in items]
    # 13.5 pages a request: 256 slots hold 3,450 of 6,144; the longest
    # request fits a slot's 64
    assert round(sum(pages) / len(pages), 1) == 13.5 and max(pages) <= 64
    # the outputs weigh the prompts least under this key of 4001-4099
    import numpy as np

    def corr(key):
        it = traffic_lib.epoch({**tr, "pairing_key": key})
        return abs(np.corrcoef([i["prompt_len"] for i in it],
                               [i["max_new"] for i in it])[0, 1])
    assert corr(tr["pairing_key"]) == min(corr(k) for k in range(4001, 4100))


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "rehearse.py"),
         "--workload", CELL, "--trace", "1", "--seconds", "3"],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["failed"] == 0
    for name in ("slot_state_bytes_per_slot", "batch_occupancy",
                 "prefill_time_share", "decode_ahead_share"):
        assert name in line["would_report"], name
    assert any("bytes of recurrent state a slot" in c[0] and c[3]
               for c in line["checks"])


def test_known_answers_on_programs_recorded_on_the_chip():
    """tests/data/jamba_prefill_two_steps.json.gz: a prefill of the 1,024
    bucket (its scans the Pallas kernel) and the two decode programs after
    it, cut from the cell's first traced run on the chip (PR 42;
    scripts/pr42_cut_trace.py, names of 420 characters: the paged call's
    pool is its fifth operand). The one-step update is TWO fusions a layer
    (y, and the update with its write in place), both over the state part;
    the paged call is the latent kernel over the shared row."""
    with gzip.open(os.path.join(HERE, "data",
                                "jamba_prefill_two_steps.json.gz")) as f:
        rec = json.loads(f.read())
    run = _run(rec["trace"], spans=[
        _span("engine.prefill", 0.05, 0.09, scan_len=1024, scan_chunks=4),
        _span("engine.decode", 0.1, 0.2, state_rows=255),
        _span("engine.decode", 0.3, 0.4, state_rows=256)])
    run["loop"] = types.SimpleNamespace(
        steps=[(0.1 * i, 0.1 * i + 0.05, 0, 256, 154_000, 0) for i in (1, 3)])
    own, busy = recurrent._ops_of(run, "decode")
    assert busy == pytest.approx(0.051472806)       # two programs of 25.7 ms
    assert sum(1 for n, _s in own if re.search(
        r"^%decode[\w.]* = bf16\[256,20,128\].*custom-call\(.*"
        r"bf16\[2,6145,64,256\]", n)) == 4          # two layers, two steps
    assert sum(1 for n, _s in own if re.search(
        r"dynamic-update-slice_fusion[\w.]* = f32\[26,256,16,5120\]", n)) \
        == 2 * 26
    assert _read(run, "ssm_device_share") == pytest.approx(55.358, abs=1e-3)
    # 511 rows x 2 x 9,318,400 B in 20.86 ms
    got = _read(run, "ssm_state_roofline")
    assert got == pytest.approx(
        100 * 511 * 2 * 9_318_400 / PEAK / 0.020859457)
    assert 55 < got < 57
    # one bucket of 1,024: 26 kernel calls, 5.64 ms
    own_p, busy_p = recurrent._ops_of(run, "prefill")
    assert busy_p == pytest.approx(0.043236396)
    assert sum(1 for n, _s in own_p if n.startswith("%selective_scan")) == 26
    got = _read(run, "ssm_scan_roofline")
    assert got == pytest.approx(
        100 * 26 * (1024 * (3 * 5120 * 2 + 128) + 327_680) / PEAK
        / 0.005636211)
    assert 17 < got < 19
    # 308,000 context tokens x 1,024 B in 2.74 ms
    got = _read(run, "paged_attn_mqa_roofline")
    assert got == pytest.approx(100 * 308_000 * 1024 / PEAK / 0.002737441)
    assert 13 < got < 15
    # the shared reader on the same programs: the sampler over [256,65536]
    assert hybrid.decode_op_share(
        run, **_spec("sampler_device_share")["args"]) \
        == pytest.approx(13.178, abs=1e-3)

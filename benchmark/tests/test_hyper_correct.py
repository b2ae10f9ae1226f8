"""`correct` of the Xing4.0 cell (runners/serve_mla_hyper.py: serve_hybrid's
comparison under the program's own routing) holds what it says: a sound
run passes both limits, the fp8 control fails the gap, and the program
with its hyper-connections, its low-rank query or YaRN's scale broken
underneath (tools/hyper_faults.py) fails it, at a size a test run can hold
(4 layers of width 128 on four streams, 4 heads on a latent of 64 + 16, a
query rank of 48, YaRN by 8 over an original context of 16, 16 experts, 3
a token; the weights' scale raised so that the layers weigh what they
weigh at width 3584).

Readings on the CPU, bfloat16 program, PR 49 (seeds 5 and 7): sound gap
0.024-0.038, shortfall 0.007-0.015; one Sinkhorn iteration for 20 reads
0.26-0.50 (the least of the five faults: its rows still sum to 1). The
readings at the cell's own sizes, on the chip, and the limits set from
them are in PERF.md."""
import json
import time

import pytest

from benchmark.lib import harness
from benchmark.tools import hyper_faults

CELL = "xing4_29b_a4b_serve.longin_closed64"
GAP, SHORT = 0.15, 0.05
SIZES = {"config": {
    "vocab_size": 4096, "hidden_size": 128, "intermediate_size": 320,
    "moe_intermediate_size": 64, "num_hidden_layers": 4,
    "n_routed_experts": 16, "num_experts_per_tok": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
    "kv_lora_rank": 64, "q_lora_rank": 48,
    "sizes_assumed": {"initializer_range": 0.08},
    "correct": {"sample_requests": 24, "gap_limit": GAP,
                "shortfall_limit": SHORT}},
    "traffic": {"output": {"dist": "lognormal", "median": 24, "sigma": 0.3,
                           "min": 16, "max": 40}}}


def _run(capsys, seed, control=None):
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "5"], time.perf_counter(), rehearsal=True,
                      control=control, overrides=SIZES)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _failed(line):
    return [c[0] for c in line["checks"] if not c[3]]


def test_sound_run_passes_and_the_fp8_control_fails_the_gap(capsys):
    line = _run(capsys, 5, control="fp8")
    assert line["correct"] is True, (_failed(line), line["program"])
    assert line["control"]["gap"] > GAP
    # untraced: the end-to-end metrics alone
    assert "hyper_mix_roofline" not in line["would_report"]


@pytest.mark.parametrize("fault", hyper_faults.FAULTS)
def test_a_broken_program_is_not_correct(capsys, fault):
    with hyper_faults.fault(fault):
        line = _run(capsys, 7)
    assert line["correct"] is False
    assert any("widest gap" in name for name in _failed(line)), _failed(line)

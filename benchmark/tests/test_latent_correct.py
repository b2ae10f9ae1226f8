"""`correct` of the latent cell (runners/serve_mla.py: serve_hybrid's
comparison under the program's own routing) holds what it says: a sound
run passes both limits, the fp8 control fails the gap, and the program
with its latent attention or its expert layer broken underneath
(tools/latent_faults.py) fails it, at a size a test run can hold (4 layers
of width 128, 4 heads on a latent of 64 + 16, 16 experts, 3 a token; the
weights' scale raised so that the layers weigh what they weigh at width
2048).

Readings on the CPU, bfloat16 program, PR 32 (seeds 5-7): sound gap
0.023-0.026, shortfall 0.007-0.012; fp8 control 0.84-0.86; the shared
experts left out 3.34, decode scaled by the absorbed width 1.29, kr
without its rotation 2.91. The readings at the cell's own sizes, on the
chip, and the limits set from them are in PERF.md."""
import json
import time

import pytest

from benchmark.lib import harness
from benchmark.tools import latent_faults

CELL = "kanana2_30b_a3b_serve.longdoc_closed128"
GAP, SHORT = 0.3, 0.08
SIZES = {"config": {
    "vocab_size": 4096, "hidden_size": 128, "intermediate_size": 320,
    "moe_intermediate_size": 64, "num_hidden_layers": 4,
    "n_routed_experts": 16, "num_experts_per_tok": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "qk_head_dim": 48,
    "v_head_dim": 32, "kv_lora_rank": 64,
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
    assert line["correct"] is True, _failed(line)
    assert line["control"]["gap"] > GAP
    assert "paged_attn_latent_roofline" not in line["would_report"]


@pytest.mark.parametrize("fault", latent_faults.FAULTS)
def test_a_broken_program_is_not_correct(capsys, fault):
    with latent_faults.fault(fault):
        line = _run(capsys, 7)
    assert line["correct"] is False
    assert any("widest gap" in name for name in _failed(line)), _failed(line)


def test_the_faults_leave_the_program_as_they_found_it():
    from paddle_tpu.models import deepseek_v3 as ds
    from paddle_tpu.serving import model
    sound = lambda: (ds.routed_ffn, ds.latent_projections,
                     model.paged_latent_attention_decode)
    before = sound()
    for name in latent_faults.FAULTS:
        with latent_faults.fault(name):
            assert sound() != before
    assert before == sound()
    with pytest.raises(ValueError, match="unknown fault"):
        with latent_faults.fault("no_such"):
            pass

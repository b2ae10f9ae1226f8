"""`correct` of the recurrent cell (runners/serve_recurrent.py: GPT's
comparison, the widest gap of a served greedy token's logit below the
float32 reference's best, read from requests that were prefilled by the
chunked scan into a slot another request had held and then decoded through
that slot's state, beside the engine's gauge of the bytes a slot's state
holds) holds what it says: a sound run passes, the fp8 control fails the
gap, and the program with its state's precision, its padding mask, its slot
reset, its taps, an inner norm or its skip broken underneath
(tools/recurrent_faults.py) fails it, at a size a test run can hold (6
layers of width 128 with attention at layer 3, 256 channels of state 8, 4
slots; the weights' scale raised so that the layers weigh what they weigh
at width 2560).

The readings on the CPU are the last lines of the module's log; those at
the cell's own sizes, on the chip, and the limit set from them are in
PERF.md and in the configuration's file."""
import json
import time

import pytest

from benchmark.lib import harness
from benchmark.tools import recurrent_faults

CELL = "jamba2_3b_serve.chat_closed512"
GAP, MEAN = 0.25, 0.0015
SIZES = {"config": {
    "vocab_size": 4096, "hidden_size": 128, "intermediate_size": 320,
    "num_hidden_layers": 6, "attn_layer_period": 6, "attn_layer_offset": 3,
    "num_attention_heads": 4, "mamba_d_state": 8, "mamba_dt_rank": 16,
    "sizes_assumed": {"initializer_range": 0.08, "head_dim": 32},
    "correct": {"sample_requests": 24, "gap_limit": GAP,
                "mean_gap_limit": MEAN}},
    "traffic": {"output": {"dist": "lognormal", "median": 24, "sigma": 0.3,
                           "min": 16, "max": 40}}}


def _run(capsys, seed, control=None):
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "5"], time.perf_counter(), rehearsal=True,
                      control=control, overrides=SIZES)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def _failed(line):
    return [c[0] for c in line["checks"] if not c[3]]


def _gap(out):
    return [l for l in out if l.startswith(("check widest gap",
                                            "check mean gap", "reference:"))]


def test_sound_run_passes_and_the_fp8_control_fails_the_gap(capsys):
    line, out = _run(capsys, 5, control="fp8")
    print(_gap(out), line["control"])
    assert line["correct"] is True, _failed(line)
    assert line["control"]["gap"] > GAP
    # per-layer metrics that need the device trace stay out of a CPU run
    assert "ssm_state_roofline" not in line["would_report"]


@pytest.mark.parametrize("fault", recurrent_faults.FAULTS)
def test_a_broken_program_is_not_correct(capsys, fault):
    with recurrent_faults.fault(fault):
        line, out = _run(capsys, 7)
    print(fault, _gap(out))
    assert line["correct"] is False
    # a state in bfloat16 moves a served token by less than the weights'
    # own rounding does (PERF.md, PR 42): the engine's gauge of the bytes
    # a slot holds is what reads it; every other fault fails the gaps
    by = "bytes of recurrent state" if fault == "state_bf16" else "widest"
    assert any(by in name for name in _failed(line)), _failed(line)

"""`correct` of the looped cell (runners/serve_looped.py, GPT's comparison
as it stands) holds what it says: a sound run passes the limit, the fp8
control fails it, and the program with its loop over the passes broken
underneath (tools/loop_faults.py) fails it, at a size a test run can hold
(4 layers x 4 passes of width 128; the weights' scale raised so that the
layers weigh what they weigh at width 2048).

Readings on the CPU, bfloat16 program, PR 30 (seeds 5-7): sound gap
0.021-0.069; fp8 control 1.18-1.85; a program of three passes 3.01-3.49;
every pass attending over pass 0's K/V 5.68-6.02. The readings at the
cell's own sizes, on the chip, and the limit set from them are in PERF.md."""
import json
import time

import pytest

from benchmark.lib import harness
from benchmark.tools import loop_faults

CELL = "ouro_2p6b_serve.decode_closed32"
LIMIT = 0.3
SIZES = {"config": {
    "vocab_size": 4096, "hidden_size": 128, "intermediate_size": 320,
    "num_hidden_layers": 4, "layer_types": ["full_attention"] * 4,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
    "sizes_assumed": {"initializer_range": 0.08},
    "correct": {"sample_requests": 24, "gap_limit": LIMIT}},
    "traffic": {"output": {"dist": "lognormal", "median": 24, "sigma": 0.3,
                           "min": 16, "max": 40}}}


def _run(capsys, seed, control=None):
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "6"], time.perf_counter(), rehearsal=True,
                      control=control, overrides=SIZES)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _failed(line):
    return [c[0] for c in line["checks"] if not c[3]]


@pytest.mark.parametrize("seed", [5, 6])
def test_sound_run_passes_and_the_fp8_control_fails_the_gap(capsys, seed):
    line = _run(capsys, seed, control="fp8")
    assert line["correct"] is True, _failed(line)
    assert line["control"]["gap"] > LIMIT
    assert "loop_passes_per_token" not in line["would_report"]  # no trace


@pytest.mark.parametrize("fault", loop_faults.FAULTS)
def test_a_broken_loop_is_not_correct(capsys, fault):
    with loop_faults.fault(fault):
        line = _run(capsys, 7)
    assert line["correct"] is False
    assert any("widest gap" in name for name in _failed(line)), _failed(line)


def test_the_faults_leave_the_program_as_they_found_it():
    from benchmark.runners import serve_looped
    from paddle_tpu.serving import model
    before = (serve_looped.model_config, model.LoopedDecodeModel.decode,
              model.paged_attention_decode)
    for name in loop_faults.FAULTS:
        with loop_faults.fault(name):
            pass
    assert before == (serve_looped.model_config,
                      model.LoopedDecodeModel.decode,
                      model.paged_attention_decode)
    with pytest.raises(ValueError, match="unknown fault"):
        with loop_faults.fault("no_such"):
            pass

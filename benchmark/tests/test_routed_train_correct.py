"""`correct` of the trained routed cell (runners/train_routed.py: train.py's
three compared steps under the program's own routing, and the shortfall)
holds what it says: a sound run passes all four limits, the fp8 control
fails, and the program with its band, its positions, its router, its
share or its q/k norm broken underneath (tools/routed_train_faults.py)
fails one at least, at a size a test run can hold (4 layers of width 128,
4 heads of 32 over 2 KV heads, a window of 16 in sequences of 128, 16
experts, 4 a token, 4 held; the weights' scale raised so that the layers
weigh what they weigh at width 2304).

Readings on the CPU, bfloat16 program, PR 46 (seeds 5-7; loss gap, first
gradient's worst leaf, change's worst leaf, shortfall): sound at most
0.0007, 0.0033, 0.0007, 0.0037; the fp8 control 0.0101, 0.0110, 0.0044,
0.0374; the faults, seed 7: band one short 0.0120 0.0256 0.0027 0.0794,
a sliding layer attending to everything 0.0573 0.389 0.0153 0.152, YaRN
on the sliding layers 0.0051 0.603 0.0102 0.128, YaRN left off the full
layer 0.0143 0.0600 0.0030 0.0582, the 1.277 factor dropped 0.0080 0.0485
0.0028 0.0424, sigmoid for softmax 0.0036 0.513 0.0060 0.0501, the
weights not normalised 0.0102 0.409 0.0042 0.0343, the next share's
experts 0.0280 0.152 0.0104 0.0779, the q/k norm dropped 0.0123 0.247
0.0633 0.0772. The readings at the cell's own sizes, on the chip, and the
limits set from them are in PERF.md."""
import json
import time

import pytest

from benchmark.lib import harness
from benchmark.tools import routed_train_faults

CELL = "mellum2_12b_a2p5b_train.b2s8192"
LIMITS = {"loss_gap_limit": 0.002, "grad_norm_limit": 0.01,
          "change_norm_limit": 0.002, "shortfall_limit": 0.012}
SIZES = {"config": {
    "hidden_size": 128, "moe_intermediate_size": 64, "num_experts": 16,
    "num_experts_per_tok": 4, "experts_held": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "sliding_window": 16,
    "vocab_size": 1024, "sizes_assumed": {"initializer_range": 0.08},
    "correct": {"row_block": 32, **LIMITS}},
    "traffic": {"batch": 2, "seq": 128}}


def _run(capsys, seed, control=None):
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "1"], time.perf_counter(), rehearsal=True,
                      control=control, overrides=SIZES)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def _failed(line):
    return [c[0] for c in line["checks"] if not c[3]]


def test_sound_run_passes_and_the_fp8_control_fails(capsys):
    line, out = _run(capsys, 5, control="fp8")
    assert line["correct"] is True, _failed(line)
    # every number beside its limit: the three of train.py and the
    # shortfall, under replay
    names = [c[0] for c in line["checks"]]
    assert sum("widest" in n or "norm" in n for n in names) == 4
    ctrl = line["control"]
    assert ctrl["loss_gap"] > 2 * LIMITS["loss_gap_limit"]
    assert ctrl["shortfall"] > 2 * LIMITS["shortfall_limit"]
    tally = next(l for l in out if l.startswith("tally:"))
    assert "held here" in tally


@pytest.mark.parametrize("fault", routed_train_faults.FAULTS)
def test_a_broken_program_is_not_correct(capsys, fault):
    with routed_train_faults.fault(fault):
        line, _ = _run(capsys, 7)
    assert line["correct"] is False
    assert any("widest" in name or "norm" in name
               for name in _failed(line)), _failed(line)


def test_the_faults_leave_the_program_as_it_was():
    from paddle_tpu.models import mellum
    before = (mellum.attend, mellum.rope_table, mellum.routed_ffn,
              mellum.rmsnorm)
    for name in routed_train_faults.FAULTS:
        with routed_train_faults.fault(name):
            pass
    assert before == (mellum.attend, mellum.rope_table, mellum.routed_ffn,
                      mellum.rmsnorm)
    with pytest.raises(ValueError, match="unknown fault"):
        with routed_train_faults.fault("nothing"):
            pass

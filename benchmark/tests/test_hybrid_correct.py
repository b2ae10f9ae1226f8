"""`correct` of the hybrid cell (runners/serve_hybrid.py) holds what it
says: a sound run passes both limits, the fp8 control fails the gap, and
the program with its expert layer broken underneath (tools/faults.py)
fails one of the two, at a size a test run can hold (between the tiny
rehearsal and the cell; the weights' scale raised so that the layers
weigh what they weigh at width 2048).

Readings on the CPU, bfloat16 program, PR 26 (seeds 5-7): sound gap under
the program's routing 0.051-0.059, shortfall 0.023; fp8 control 1.08-1.16;
the sound program against the reference's OWN routing 0.75-0.77 (the
program chose another set in 2.1% of the routed token-layers), which is
why the comparison replays the program's routing: the free one cannot tell
a sound run from the control. Broken: weights from s + b 0.145, no
normalisation 0.84, a dropped pair 1.06 (gap); selection on s alone
shortfall 0.25 with a sound gap (0.050). The readings at the cell's own
sizes, on the chip, and the limits set from them are in PERF.md."""
import json
import time

import pytest

from benchmark.lib import harness
from benchmark.tools import faults

CELL = "lfm2_8b_a1b_serve.decode_closed128"
LIMITS = {"gap_limit": 0.1, "shortfall_limit": 0.08}
SIZES = {"config": {
    "vocab_size": 4096, "hidden_size": 128, "intermediate_size": 320,
    "moe_intermediate_size": 96, "num_attention_heads": 8,
    "num_key_value_heads": 2,
    "sizes_assumed": {"head_dim": 16, "initializer_range": 0.08},
    "correct": {"sample_requests": 24, **LIMITS}},
    "traffic": {"output": {"dist": "lognormal", "median": 24, "sigma": 0.3,
                           "min": 16, "max": 40}}}
GAP = "widest gap"
SHORT = "widest shortfall"


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
    assert line["control"]["gap"] > LIMITS["gap_limit"]
    # the comparison against the reference's own routing would not do: a
    # sound run reads as wide as the control there
    assert line["control"]["free_routing_gap"] > LIMITS["gap_limit"]
    assert 0.0 < line["control"]["flips"] < 0.1


@pytest.mark.parametrize("fault,check", [
    ("select_on_s", SHORT), ("weigh_by_biased", GAP), ("no_normalise", GAP),
    ("drop_pair", GAP)])
def test_a_broken_expert_layer_is_not_correct(capsys, fault, check):
    with faults.fault(fault):
        line = _run(capsys, 7)
    assert line["correct"] is False
    assert any(check in name for name in _failed(line)), _failed(line)
    if fault == "select_on_s":
        # a wrong router computes sound logits under its own routing:
        # only the shortfall shows it
        assert not any(GAP in name for name in _failed(line))

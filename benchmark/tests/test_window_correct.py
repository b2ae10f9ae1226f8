"""`correct` of the windowed cell (runners/serve_window.py: serve_hybrid's
comparison under the program's own routing, on a sample that holds
requests whose rings have wrapped and one that never left the window)
holds what it says: a sound run passes both limits, the fp8 control fails
the gap, and the program with its windows, its ring, its gate, its RoPE or
its expert layer broken underneath (tools/window_faults.py) fails it, at a
size a test run can hold (6 layers of width 128, 4 heads of 32 over 2 KV
heads, a window of 16 in pages of 8, 16 experts, 3 a token; the weights'
scale raised so that the layers weigh what they weigh at width 2048).

Readings on the CPU, bfloat16 program, PR 40 (seeds 5-7): sound gap
0.054-0.060, shortfall 0.007-0.011; fp8 control 0.96; a window layer
attending to everything 4.13 (shortfall 0.82), the full layer windowed 2.38
(0.31), RoPE on the full layer 1.0 or more (0.15), the gate left out (0.42),
the shared expert left out (0.35), the ring read a page short: the last
lines of the module's log. The readings at the cell's own sizes, on the
chip, and the limits set from them are in PERF.md."""
import json
import time

import pytest

from benchmark.lib import harness
from benchmark.tools import window_faults

CELL = "trinity_mini_serve.shortlong_closed128"
GAP, SHORT = 0.3, 0.08
SIZES = {"config": {
    "vocab_size": 4096, "hidden_size": 128, "intermediate_size": 320,
    "moe_intermediate_size": 64, "num_experts": 16, "num_experts_per_tok": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "sliding_window": 16,
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
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def _failed(line):
    return [c[0] for c in line["checks"] if not c[3]]


def test_sound_run_passes_and_the_fp8_control_fails_the_gap(capsys):
    line, out = _run(capsys, 5, control="fp8")
    assert line["correct"] is True, _failed(line)
    assert line["control"]["gap"] > GAP
    assert "paged_attn_window_roofline" not in line["would_report"]
    # the sample holds contexts past two windows and one inside the window
    sample = next(l for l in out if l.startswith("sample: contexts"))
    ctx = [int(x) for x in sample.split("contexts")[1].split("(")[0].split()]
    assert sum(c > 32 for c in ctx) >= 2 and max(ctx) > 64


@pytest.mark.parametrize("fault", window_faults.FAULTS)
def test_a_broken_program_is_not_correct(capsys, fault):
    with window_faults.fault(fault):
        line, _ = _run(capsys, 7)
    assert line["correct"] is False
    assert any("widest" in name for name in _failed(line)), _failed(line)

"""The control of `correct` comes out as not correct: the reference put in
the program's place and computed one precision below the configuration's
(fp8 for bfloat16) fails a limit that sound runs of the program keep, on
three seeds, at a size a test run can hold. The readings at the cells' own
sizes, on the chip, and the limits set from them are in PERF.md."""
import json
import time

import pytest

from benchmark.lib import harness

# sizes between the tiny rehearsal and the cell: enough near-ties among
# 4096 logits for a rounding to move an argmax (readings over four seeds
# on the CPU, PR 23: program 0.0023-0.0055, fp8 control 0.024-0.085)
SERVE = {"config": {"sizes": {
    "vocab_size": 4096, "hidden_size": 256, "num_layers": 6, "num_heads": 4,
    "head_dim": 64, "intermediate_size": 1024,
    "max_position_embeddings": 128},
    "correct": {"sample_requests": 48, "gap_limit": 0.012}},
    "traffic": {"output": {"dist": "lognormal", "median": 32, "sigma": 0.3,
                           "min": 24, "max": 48}}}
# tiny sizes (readings over three seeds on the CPU, PR 23: first-gradient
# norm, worst leaf: program 0.0005-0.0023, fp8 control 0.0073-0.0118)
TRAIN = {"config": {"correct": {"grad_norm_limit": 0.004}}}


def _run(capsys, workload, seed, seconds, overrides):
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds)], time.perf_counter(),
                      rehearsal=True, control="fp8", overrides=overrides)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_serving_fp8_control_fails_the_gap_limit(capsys, seed):
    line = _run(capsys, "gpt_1p3b_serve.decode_closed64", seed, 8, SERVE)
    limit = SERVE["config"]["correct"]["gap_limit"]
    assert line["correct"] is True
    assert line["control"]["gap"] > limit


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_training_fp8_control_fails_the_gradient_limit(capsys, seed):
    line = _run(capsys, "gpt_350m_train.b16s1024", seed, 2, TRAIN)
    limit = TRAIN["config"]["correct"]["grad_norm_limit"]
    assert line["correct"] is True
    assert line["control"]["grad_norm"] > limit

"""A share of a peak or of a whole cannot pass its file's `max`: a reading
above it means the count or the time is at fault, and fails the run."""
import argparse
import glob
import json
import os

from benchmark.lib import harness


def test_a_reading_above_its_files_maximum_fails_the_run(capsys):
    cell = harness.load_cell("gpt_350m_train.b16s1024")
    args = argparse.Namespace(seed=1, seconds=1, trace=1)
    ctx = harness.Context(cell, args, 0.0)
    run = {"kind": "train", "step_seconds": [1e-6] * 3, "chips": 1,
           "traffic": cell["traffic"], "config": cell["config"],
           "device_kind": "TPU v5 lite", "gate": {}, "trace": None,
           "memory_peak_bytes": 2**30}
    ctx.check("a sound check", 0, 0)
    out = harness.layer_metrics(ctx, run)   # a step of a microsecond
    assert out["mfu"]["value"] > 100 and not ctx.correct
    assert any("mfu" in c[0] and not c[3] for c in ctx.checks)


def test_every_percentage_states_its_maximum():
    for f in glob.glob(os.path.join(harness.BENCH_DIR, "layer_metrics",
                                    "*.json")):
        spec = json.load(open(f))
        assert (spec["unit"] != "%") or spec["max"] == 100, f

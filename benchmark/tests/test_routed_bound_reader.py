"""`train_pairs_bound_hit_share` (readers/routed_bound.py) from the
trainer's tally: 100 where no layer's held pairs passed the bound, the
share of layer-steps that fitted it otherwise, None from a tally without
the keys (the parent of the PR that added them) or with no step taken."""
import json
import os

import pytest

from benchmark.readers import routed_bound

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = "train_pairs_bound_hit_share"
CELL = "mellum2_12b_a2p5b_train.b2s8192"
PARENTS = {"pairs_routed": 4 * 131072 * 80, "pairs_held": 4 * 32768 * 80,
           "experts_held": list(range(16)),
           "held_counts": [[2048 * 80] * 16] * 4}


@pytest.mark.parametrize("tally,want", [
    (dict(PARENTS, steps=80, rows_bound=65536,
          layer_steps_over_bound=[0, 0, 0, 0]), 100.0),
    (dict(PARENTS, steps=80, rows_bound=65536,
          layer_steps_over_bound=[0, 0, 8, 24]), 90.0),
    (dict(PARENTS, steps=80, rows_bound=65536,
          layer_steps_over_bound=[80, 80, 80, 80]), 0.0),
    (PARENTS, None),                    # the parent's tally: no such keys
    (dict(PARENTS, steps=0, rows_bound=None,
          layer_steps_over_bound=[0, 0, 0, 0]), None),
    (None, None)])
def test_the_share_of_layer_steps_that_fitted_the_bound(tally, want):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           NAME + ".json")) as f:
        spec = json.load(f)
    mod, fn = spec["reader"].split(":")
    assert mod == "routed_bound"
    got = getattr(routed_bound, fn)({"tally": tally}, **spec["args"])
    assert got == want
    assert got is None or 0 <= got <= spec["max"] == 100


def test_benchmark_json_lists_the_metric_for_its_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter",
                 "layer": "expert layer, parallel/moe.py",
                 "moves": "train_tok_s_chip", "workloads": [CELL]}

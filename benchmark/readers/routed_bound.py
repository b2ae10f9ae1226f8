"""Reader for the bound an expert-parallel rank puts on the rows of its
sorted token-expert pairs (`paddle_tpu/parallel/moe.py::held_rows_bound`):
how often a layer's held pairs fitted it. The trainer counts, beside its
tally of choices and from the same choices, the steps in which a layer's
held pairs passed the bound (such a layer takes the whole-size path that
step); `tally_stats()` hands back `steps`, `rows_bound` and
`layer_steps_over_bound`, and the runner passes the dict through whole.
Without those keys (a program from before the bound) it returns None.
"""
from __future__ import annotations


def pairs_bound_hit_share(run):
    """Of the window's layer-steps (steps taken x expert layers), the share
    whose held pairs fitted the bound: 100 where the short path ran on
    every layer of every step."""
    t = run.get("tally") or {}
    over, steps = t.get("layer_steps_over_bound"), t.get("steps")
    if not over or not steps:
        return None
    return 100.0 * (1.0 - sum(over) / (steps * len(over)))

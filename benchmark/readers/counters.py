"""Readers of the harness's own counters and clocks (no trace needed).
Each takes the runner's `run` record and returns a number, or None where
this run has nothing for it to read."""
from __future__ import annotations

from ..lib import peaks, stats


def _serve_steps(run):
    loop = run.get("loop")
    if loop is None:
        return None
    t0, t1 = run["window"]
    return [s for s in loop.steps if t0 < s[1] <= t1]


def gen_late_p99_ms(run):
    """How late the generator sent an arrival, sent minus due."""
    loop = run.get("loop")
    if loop is None or run["traffic"]["loop"] != "open":
        return None
    t0, t1 = run["window"]
    late = [1e3 * d for due, d in loop.late if t0 <= due < t1]
    return stats.percentile(late, 99)


def batch_occupancy(run):
    """Live slots over slots, mean over the window's decode steps."""
    steps = _serve_steps(run)
    if not steps:
        return None
    live = [s[3] for s in steps if s[3] > 0]
    return 100.0 * sum(live) / (len(live) * run["slots"]) if live else None


def pool_reserved_peak(run):
    loop = run.get("loop")
    if loop is None:
        return None
    t0, t1 = run["window"]
    used = [u for t, u in loop.pool_used if t0 <= t < t1]
    return 100.0 * max(used) / run["pool_pages"] if used else None


def decode_step_p50_ms(run):
    """Host clock round `Engine.step` (which ends in the token read), over
    the window's steps that admitted nothing."""
    steps = _serve_steps(run)
    if not steps:
        return None
    d = [1e3 * (s[1] - s[0]) for s in steps if s[2] == 0 and s[3] > 0]
    return stats.median(d)


def _gaps_ms(run):
    loop = run.get("loop")
    if loop is None:
        return None
    t0, t1 = run["window"]
    return [1e3 * g for at, g in loop.gaps if t0 <= at < t1]


def itl_percentile_ms(run, p):
    gaps = _gaps_ms(run)
    return stats.percentile(gaps, p) if gaps else None


def ttft_percentile_ms(run, p):
    """First token minus the time the arrival was due, over the arrivals
    of the window (open loop); one that got none lies beyond every
    percentile and the metric is then left out."""
    v = run.get("ttft_ms")
    if not v or run["traffic"]["loop"] != "open":
        return None
    x = stats.percentile(v, p)
    return x if x != float("inf") else None


def slice_rate_p50(run):
    """The median slice's rate: steadier than the window's mean against
    one rare stall, which is the tails' business."""
    rates = run.get("slice_rates")
    return stats.median(rates) if rates else None


def gate_keys_pallas(run, kind):
    """Keys of this run's kernel gate held by a Pallas candidate."""
    if run.get("kind") != kind:
        return None
    return sum(1 for w in run["gate"].values() if w == "pallas")


def peak_hbm_gib(run, kind):
    if run.get("kind") != kind:
        return None
    return run["memory_peak_bytes"] / 2**30


def train_step_p50_ms(run):
    if "step_seconds" not in run:
        return None
    return 1e3 * stats.median(run["step_seconds"])


def mfu(run):
    """Model FLOP/s utilization: the step's matmul FLOPs (no recompute)
    over the median step, over chips times the bf16 peak."""
    if "step_seconds" not in run:
        return None
    tr = run["traffic"]
    flops = peaks.gpt_train_flops_per_step(run["config"]["sizes"],
                                           int(tr["batch"]), int(tr["seq"]))
    peak = peaks.peak(run["device_kind"])["flops_bf16"]
    return 100.0 * flops / stats.median(run["step_seconds"]) \
        / (run["chips"] * peak)

"""Readers for a trained decoder of banded and full grouped-query attention
and routed experts of which a share is held (configs/
mellum2_12b_a2p5b_train.json). The device readers take an operation by the
name the profiler gives it, which for a Pallas call is the kernel's:
`%flash_band_{fwd,dq,dkv}` and `%flash_full_{fwd,dq,dkv}` (ops/
pallas_attention.py names the banded and grouped calls so), `%gmm` and
`%tgmm` (megablox's grouped products, forward and both backward forms);
the optimizer's operations are those that write a float32 array of a
parameter leaf's shape beside another (the parameter and its moments). The
counters come from the trainer's `tally_stats()` (pairs routed, pairs held
here, each held expert's count), read once after the window. Without a
trace, a tally or such an operation (the parent of the PR that added them)
each returns None.
"""
from __future__ import annotations

import importlib
import re

from ..lib import peaks, routed_train_counts as counts, stats
from ..lib.trace import self_times

FLASH = r"^%flash_{kind}_(fwd|dq|dkv)[.\d]* = "
GROUPED = re.compile(r"^%t?gmm[.\d]* = ")


def _own_ops(run):
    """[(name, seconds)] of the traced window's operations by own time
    (chip 0: one chip), and the device's busy seconds."""
    t = run.get("trace")
    if t is None or not t.devices:
        return None, 0.0
    dev = t.devices[min(t.devices)]
    own = [(n, ns / 1e9) for n, ns in self_times(t._in_window(dev["ops"]))]
    return own, t.busy_s


def _tally(run):
    t = run.get("tally")
    return t if t and t.get("pairs_routed") else None


def _shape(run):
    tr = run["traffic"]
    return run["config"]["sizes"], int(tr["batch"]), int(tr["seq"])


def _pairs_held_a_step(run) -> float | None:
    """Pairs held here in one step, summed over the layers: the tally's
    held share of the pairs a step routes."""
    t = _tally(run)
    if t is None:
        return None
    s, batch, seq = _shape(run)
    routed = batch * seq * s["num_experts_per_tok"] * s["num_hidden_layers"]
    return routed * t["pairs_held"] / t["pairs_routed"]


def pairs_held_share(run):
    t = _tally(run)
    return None if t is None else \
        100.0 * t["pairs_held"] / t["pairs_routed"]


def expert_load_max_over_mean(run):
    """Pairs of the busiest held expert of a layer over the mean of the
    layer's held experts, mean over the layers (1.0 = even)."""
    t = _tally(run)
    if t is None:
        return None
    ratios = [max(row) * len(row) / sum(row) for row in t["held_counts"]
              if sum(row) > 0]
    return sum(ratios) / len(ratios) if ratios else None


def mfu_routed(run):
    """The step's model FLOPs (attention over what the mask lets through,
    the experts over the pairs the tally says were held; no recompute)
    over the median step over the bf16 peak."""
    held = _pairs_held_a_step(run)
    if held is None or "step_seconds" not in run:
        return None
    s, batch, seq = _shape(run)
    flops = counts.step_flops(s, batch, seq, held)
    return 100.0 * flops / stats.median(run["step_seconds"]) \
        / (run["chips"] * peaks.peak(run["device_kind"])["flops_bf16"])


def _flash_calls(run, kind):
    own, busy = _own_ops(run)
    if not own:
        return None, busy
    rx = re.compile(FLASH.format(kind=kind))
    return [(m.group(1), sec) for n, sec in own
            for m in [rx.search(n)] if m], busy


def flash_roofline(run, kind):
    """FLOPs the traced flash calls of one kind of layer ("band" or
    "full") must do, each call's products over the (row, key) pairs its
    mask lets through, at the bf16 peak over their device time. Bound by
    compute."""
    calls, _busy = _flash_calls(run, kind)
    if not calls:
        return None
    s, batch, seq = _shape(run)
    layer = counts.SLIDING if kind == "band" else counts.FULL
    flops = sum(counts.flash_call_flops(s, batch, seq, layer, call)
                for call, _sec in calls)
    secs = sum(sec for _call, sec in calls)
    return 100.0 * flops / peaks.peak(run["device_kind"])["flops_bf16"] / secs


def flash_device_share(run, kind):
    calls, busy = _flash_calls(run, kind)
    if not calls or not busy:
        return None
    return 100.0 * sum(sec for _c, sec in calls) / busy


def _grouped(run):
    own, busy = _own_ops(run)
    if not own:
        return None, busy
    return [sec for n, sec in own if GROUPED.search(n)], busy


def expert_device_share(run):
    secs, busy = _grouped(run)
    if not secs or not busy:
        return None
    return 100.0 * sum(secs) / busy


def expert_roofline(run):
    """What the traced grouped products (forward, recompute and backward:
    twelve a layer and step) need at the chip's peaks, each the larger of
    its FLOPs over the bf16 peak and its bytes over the HBM peak, over
    their device time. Rows are the pairs held in a step (the tally's
    mean), a layer's share each."""
    secs, _busy = _grouped(run)
    held = _pairs_held_a_step(run)
    if not secs or held is None:
        return None
    s, _batch, _seq = _shape(run)
    rows = held / s["num_hidden_layers"]
    peak = peaks.peak(run["device_kind"])
    need = max(counts.expert_product_flops(s, rows) / peak["flops_bf16"],
               counts.expert_product_bytes(s, rows) / peak["hbm_bytes_s"])
    return 100.0 * need * len(secs) / sum(secs)


def _leaf_shapes(run):
    import jax
    ref = importlib.import_module(
        "benchmark.reference." + run["config"]["reference"])
    shapes = jax.tree_util.tree_leaves(
        ref.weight_shapes(run["config"]["sizes"]),
        is_leaf=lambda x: isinstance(x, tuple))
    return {",".join(map(str, sh)) for sh in shapes}


def adamw_device_share(run):
    """Device time of the operations that write two or more float32
    arrays of one parameter leaf's shape (the parameter, its moments: the
    optimizer's fused update of a leaf), over the device's busy time."""
    own, busy = _own_ops(run)
    if not own or not busy:
        return None
    shapes = _leaf_shapes(run)
    f32 = re.compile(r"f32\[([\d,]+)\]")
    secs = 0.0
    for name, sec in own:
        out = name.split(" = ", 1)[-1]
        out = re.split(r" (fusion|custom-call|while|call)\(", out, 1)[0]
        got = [m for m in f32.findall(out) if m in shapes]
        if len(got) >= 2 and len(set(got)) == 1:
            secs += sec
    return 100.0 * secs / busy if secs else None

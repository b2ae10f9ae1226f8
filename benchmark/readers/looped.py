"""Readers for a looped decoder (configs/ouro_2p6b_serve.json: one stack of
layers run several times a token, K/V of every pass in the pages). As
readers/hybrid.py's, the device readers look at the DECODE programs of the
traced span only (a prefill has the same operations at other shapes), count
a `while`'s body once, and name operations by the profiler's text: the HLO
instruction with its operands, so a pattern can name an output shape or an
operand (the stacked layers' weights arrive as `bf16[L,D,F]` operands of
the fusions that slice and multiply them). Without a trace, or on a program
that has no such operation or tally, each returns None.
"""
from __future__ import annotations

from ..lib import looped_counts, peaks
from .hybrid import DECODE, _decode_ops, _seconds


def _fields(run) -> dict:
    cfg = run["config"]
    f = dict(cfg["sizes"])
    f.update(cfg.get("engine", {}))
    f["q_width"] = f["num_attention_heads"] * f["head_dim"]
    f["kv_width"] = f["num_key_value_heads"] * f["head_dim"]
    return f


def _log(run, name):
    return [e[name] for e in run.get("stats_log", ())
            if e.get(name) is not None]


def loop_passes_per_token(run):
    """Layer passes the program ran for every token it was fed: the
    engine's `loop_passes` tally (tokens of real prompt positions and live
    slots that ran pass t), last reading less first, summed over the
    passes, over the tokens the HARNESS saw go in between the two readings
    (prompt tokens prefilled and tokens decoded, every step of its loop).
    `total_ut_steps` when nothing was left out."""
    log, loop = _log(run, "loop_passes"), run.get("loop")
    if len(log) < 2 or loop is None:
        return None
    ran = sum(log[-1]) - sum(log[0])
    fed = sum(s[3] + s[5] for s in loop.steps)
    return ran / fed if fed else None


def decode_op_share(run, ops, but=()):
    """Device time of the decode programs' operations that match any of
    the patterns `ops` and none of `but`, over those programs' busy
    time."""
    own, busy = _decode_ops(run)
    if not own or not busy:
        return None
    secs = _seconds(own, ops, _fields(run), but)
    return 100.0 * secs / busy if secs else None


def paged_attn_looped_roofline(run, ops):
    """Bytes the traced decode steps' paged-attention calls must read (K
    and V of every live context token, once in every pass and layer) at the
    HBM peak, over the kernel's device time in the decode programs. Bound
    by bandwidth."""
    own, _busy = _decode_ops(run)
    if not own or "loop" not in run:
        return None
    f = _fields(run)
    secs = _seconds(own, ops, f)
    a, b = run["trace_span"]
    ctx = sum(s[4] for s in run["loop"].steps if a <= s[0] and s[1] <= b)
    if not secs or not ctx:
        return None
    need = looped_counts.paged_kv_bytes(ctx, f)
    return 100.0 * need / peaks.peak(run["device_kind"])["hbm_bytes_s"] / secs


def decode_weight_roofline(run, ops, but=()):
    """Bytes of weights the traced decode programs must read (the stacked
    layers once a pass and the head, each program) at the HBM peak, over
    the device time of the operations that read a layer's or the head's
    weights in those programs. Bound by bandwidth: a step of 16 rows does
    1 multiply-add a byte."""
    own, _busy = _decode_ops(run)
    if not own:
        return None
    f = _fields(run)
    secs = _seconds(own, ops, f, but)
    t = run["trace"]
    dev = t.devices[min(t.devices)]
    programs = sum(1 for n, _s, _d in t._in_window(dev["modules"])
                   if DECODE.search(n))
    if not secs or not programs:
        return None
    need = programs * looped_counts.decode_weight_bytes(f)
    return 100.0 * need / peaks.peak(run["device_kind"])["hbm_bytes_s"] / secs

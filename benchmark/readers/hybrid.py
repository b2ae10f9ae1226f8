"""Readers for a decoder of gated short convolutions, grouped-query paged
attention and routed experts (configs/lfm2_8b_a1b_serve.json). The device
readers look at the DECODE programs of the traced span only (the events
of line "XLA Ops" that lie inside an event `jit_decode` of line "XLA
Modules"): a prefill program has the same operations at other shapes.
Operation names are the profiler's, the HLO instruction's text with its
operands, so a pattern can name an output shape or an operand (the
unrolled layers' weights arrive as `%params__layers___<l>___<group>____
<leaf>__`). Without a trace, or on a program that has no such operation or
tally, each returns None.
"""
from __future__ import annotations

import re

from ..lib import hybrid_counts, peaks
from ..lib.trace import self_times

DECODE = re.compile(r"^jit_decode\b")


def _fields(run) -> dict:
    cfg = run["config"]
    f = dict(cfg["sizes"])
    f.update(cfg.get("engine", {}))
    f["pairs"] = f["num_slots"] * f["num_experts_per_tok"]
    f["groups"] = f["num_attention_heads"] // f["num_key_value_heads"]
    f["slot_logits"] = f["num_slots"] * f["vocab_size"]
    f["kv_width"] = 2 * f["head_dim"]
    f["in_width"] = 3 * f["hidden_size"]
    f["taps"] = f["conv_L_cache"] - 1
    f["conv_layers"] = sum(1 for k in f["layer_types"] if k == "conv")
    f["moe_layers"] = f["num_hidden_layers"] - f["num_dense_layers"]
    return f


def _decode_ops(run):
    """[(name, seconds)] of the operations inside the traced decode
    programs, and those programs' busy seconds (chip 0: one chip)."""
    t = run.get("trace")
    if t is None or not t.devices:
        return None, 0.0
    dev = t.devices[min(t.devices)]
    spans = sorted((s, s + d) for n, s, d in t._in_window(dev["modules"])
                   if DECODE.search(n))
    if not spans:
        return None, 0.0
    ops, j = [], 0
    for n, s, d in sorted(t._in_window(dev["ops"]), key=lambda e: e[1]):
        while j < len(spans) and spans[j][1] <= s:
            j += 1
        if j < len(spans) and spans[j][0] <= s and s + d <= spans[j][1]:
            ops.append((n, s, d))
    # a `while` or a call holds its body's operations: own time only
    own = [(n, ns / 1e9) for n, ns in self_times(ops)]
    return own, sum(sec for _n, sec in own)


def _seconds(ops, patterns, fields, but=()) -> float:
    rx = [re.compile(p.format(**fields)) for p in patterns]
    no = [re.compile(p.format(**fields)) for p in but]
    return sum(sec for n, sec in ops if any(r.search(n) for r in rx)
               and not any(r.search(n) for r in no))


def decode_op_share(run, ops, but=()):
    """Device time of the decode programs' operations that match any of
    the patterns `ops` and none of `but`, over those programs' busy
    time."""
    own, busy = _decode_ops(run)
    if not own or not busy:
        return None
    secs = _seconds(own, ops, _fields(run), but)
    return 100.0 * secs / busy if secs else None


def _log(run, at):
    return next((e for e in run.get("stats_log", ())
                 if e["at"] == at and e.get("expert_touched") is not None),
                None)


def moe_expert_roofline(run, ops):
    """Bytes of the experts' weights that the traced decode steps had to
    read (each expert a live slot's token reached, each layer, each step:
    the engine's `expert_touched` tally, after minus before the traced
    span) at the HBM peak, over the device time of the grouped products in
    those programs. Bound by bandwidth."""
    own, _busy = _decode_ops(run)
    a, b = _log(run, "trace_start"), _log(run, "trace_end")
    if not own or a is None or b is None:
        return None
    f = _fields(run)
    secs = _seconds(own, ops, f)
    touched = sum(map(sum, b["expert_touched"])) \
        - sum(map(sum, a["expert_touched"]))
    if not secs or not touched:
        return None
    need = touched * hybrid_counts.expert_weight_bytes(
        f["hidden_size"], f["moe_intermediate_size"])
    return 100.0 * need / peaks.peak(run["device_kind"])["hbm_bytes_s"] / secs


def paged_attn_gqa_roofline(run, ops):
    """Bytes the traced decode steps' paged-attention calls must read (K
    and V of every live context token over the KV heads, each attention
    layer held) at the HBM peak, over the kernel's device time. Bound by
    bandwidth."""
    own, _busy = _decode_ops(run)
    if not own or "loop" not in run:
        return None
    f = _fields(run)
    secs = _seconds(own, ops, f)
    a, b = run["trace_span"]
    ctx = sum(s[4] for s in run["loop"].steps if a <= s[0] and s[1] <= b)
    if not secs or not ctx:
        return None
    layers = sum(1 for k in f["layer_types"] if k == "full_attention")
    need = hybrid_counts.gqa_kv_bytes(ctx, f["num_key_value_heads"],
                                      f["head_dim"], layers)
    return 100.0 * need / peaks.peak(run["device_kind"])["hbm_bytes_s"] / secs


def expert_load_max_over_mean(run):
    """Token-expert pairs of the busiest expert of a layer over the
    layer's mean, mean over the layers, from the first to the last reading
    of the engine's `expert_tokens` tally after warm-up (1.0 = even)."""
    log = [e for e in run.get("stats_log", ())
           if e.get("expert_tokens") is not None]
    if len(log) < 2:
        return None
    ratios = []
    for first, last in zip(log[0]["expert_tokens"], log[-1]["expert_tokens"]):
        pairs = [y - x for x, y in zip(first, last)]
        if sum(pairs) <= 0:
            return None
        ratios.append(max(pairs) * len(pairs) / sum(pairs))
    return sum(ratios) / len(ratios)

"""Readers for a decoder whose attention layers are of two kinds
(configs/trinity_mini_serve.json: the sliding layers' K/V in a ring of
pages a slot, the full layer's in pages under a request's table). The
sliding layers' attention is the XLA gather over the ring, the full
layer's the Pallas kernel where the program is as this configuration builds
it, and the patterns (`layer_metrics/*.json`) take a layer kind's attention
by what it touches, whichever runs: the kind's POOL `[layers of the kind,
pool pages + 1, ps, Hkv, d]` as a result or an operand (the XLA path's
gathers, the new rows' writes, and the Pallas call, whose operands the
trace's name carries), the gathered rows `[S, positions, Hkv, d]` and the
scores `[S, Hkv, G, positions]` of the XLA path's products, positions being
a ring's or a whole table's. What is here adds those sizes to
`hybrid._fields` and needs the bytes (lib/window_counts.py) or the engine's
span attributes. As that module's, the device readers look at the DECODE
programs of the traced span only. Without a trace, or on a program that
has no such operation or attribute (the parent of the PR that added them),
each returns None.
"""
from __future__ import annotations

from ..lib import peaks, window_counts
from . import hybrid, spans
from .hybrid import _decode_ops, _seconds


def _fields(run) -> dict:
    """`hybrid._fields` and the sizes of both kinds of K/V array (the
    sliding layers': a ring a slot and one trash page)."""
    f = hybrid._fields(run)
    ps = int(f["page_size"])
    ring = window_counts.ring_pages(f, ps)
    f.update(
        sliding_layers=window_counts.layers_of(f, window_counts.SLIDING),
        full_layers=window_counts.layers_of(f, window_counts.FULL),
        window_pool_rows=int(f["num_slots"]) * ring + 1,
        global_pool_rows=int(f["num_pages"]) + 1,
        ring_positions=ring * ps,
        table_positions=min(int(f["num_pages"]),
                            int(f["max_seq_len"]) // ps) * ps)
    return f


def decode_op_share(run, ops, but=()):
    """`hybrid.decode_op_share` with this module's fields."""
    own, busy = _decode_ops(run)
    if not own or not busy:
        return None
    secs = _seconds(own, ops, _fields(run), but)
    return 100.0 * secs / busy if secs else None


def _hbm(run) -> float:
    return peaks.peak(run["device_kind"])["hbm_bytes_s"]


def _attr_sum(run, name: str, attr: str, a: float, b: float):
    """Sum of `attr` over the program's spans `name` that lie in [a, b] on
    the harness's clock; None where no such span carries it."""
    got = spans.program_spans(run)
    if not got:
        return None
    vals = [s["attrs"][attr] for s in got
            if s["name"] == name and attr in s["attrs"]
            and a <= s["start"] and s["end"] <= b]
    return sum(vals) if vals else None


def paged_attn_window_roofline(run, ops, but=()):
    """Bytes the traced decode steps' WINDOW paged attention must read (K
    and V of min(context, window) positions a live slot, the engine's
    `window_rows` on `engine.decode`, once in every sliding layer) at the
    HBM peak, over that attention's device time in the decode programs.
    The model's bytes, whatever pages the implementation copies."""
    own, _busy = _decode_ops(run)
    if not own or run.get("trace_span") is None:
        return None
    f = _fields(run)
    secs = _seconds(own, ops, f, but)
    rows = _attr_sum(run, "engine.decode", "window_rows", *run["trace_span"])
    if not secs or not rows:
        return None
    return 100.0 * window_counts.window_read_bytes(rows, f) / _hbm(run) / secs


def paged_attn_full_roofline(run, ops, but=()):
    """Bytes the traced decode steps' FULL-layer paged attention must read
    (K and V of every live context token, once in every full layer) at the
    HBM peak, over that attention's device time."""
    own, _busy = _decode_ops(run)
    if not own or "loop" not in run:
        return None
    f = _fields(run)
    secs = _seconds(own, ops, f, but)
    a, b = run["trace_span"]
    ctx = sum(s[4] for s in run["loop"].steps if a <= s[0] and s[1] <= b)
    if not secs or not ctx:
        return None
    return 100.0 * window_counts.full_read_bytes(ctx, f) / _hbm(run) / secs


def kv_bytes_per_context_token(run):
    """Bytes the live requests' RESERVED pages hold, under their tables
    and in their slots' rings (`pages_reserved` and `window_pages_reserved`
    of the window's `engine.step` spans, each times its kind's page) over the context
    tokens the same steps' decodes read (the harness's count). A program
    that kept every layer whole would read `lib/window_counts.py::
    whole_cache_bytes_a_token` or more (reserved pages hold the worst
    case)."""
    if "loop" not in run:
        return None
    a, b = run["window"]
    got = spans.program_spans(run)
    if not got:
        return None
    f = _fields(run)
    page = window_counts.page_bytes(f, int(f["page_size"]))
    held = [s["attrs"]["pages_reserved"] * page["global"]
            + s["attrs"]["window_pages_reserved"] * page["window"]
            for s in got if s["name"] == "engine.step"
            and "window_pages_reserved" in s["attrs"]
            and a <= s["start"] and s["end"] <= b]
    ctx = [s[4] for s in run["loop"].steps
           if a <= s[0] and s[1] <= b and s[3] > 0]
    if not held or not ctx:
        return None
    return (sum(held) / len(held)) / (sum(ctx) / len(ctx))

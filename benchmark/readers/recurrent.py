"""Readers for a decoder whose layers keep a recurrence's state per slot
beside a few multi-query attention layers (configs/jamba2_3b_serve.json).
The device readers take a layer's operations by the SHAPES the trace's
names carry (an HLO instruction's text with its operands' types): nothing
else in the model is `channels` (E = 5,120) or 2 E wide, so an operation
that names such an array is a mixer's; the per-slot parts have shapes of
their own, as have the scan kernel's custom call (`selective_scan`) and the
paged call. As `readers/hybrid.py`'s they look at the programs of one kind
inside the traced span (`jit_decode`, or `jit_prefill` for the scan) and
count an operation's own time. The bytes are lib/recurrent_counts.py's;
`state_rows` and `scan_len` are the engine's span attributes. Without a
trace, or on a program that has no such operation or attribute (the parent
of the PR that added them), each returns None.
"""
from __future__ import annotations

import re

from ..lib import peaks, recurrent_counts
from ..lib.trace import self_times
from . import spans
from .hybrid import _seconds

PROGRAMS = {"decode": re.compile(r"^jit_decode\b"),
            "prefill": re.compile(r"^jit_prefill\b")}


def _fields(run) -> dict:
    cfg = run["config"]
    f = dict(cfg["sizes"])
    f.update(cfg.get("engine", {}))
    E = recurrent_counts.channels(f)
    f.update(
        channels=E, in_width=2 * E,
        xproj_width=f["mamba_dt_rank"] + 2 * f["mamba_d_state"],
        taps=f["mamba_d_conv"] - 1,
        mamba_layers=recurrent_counts.mamba_layers(f),
        attention_layers=recurrent_counts.attention_layers(f),
        kv_width=2 * f["head_dim"],
        pool_rows=int(f["num_pages"]) + 1,
        table_positions=min(int(f["num_pages"]), int(f["max_seq_len"])
                            // int(f["page_size"])) * int(f["page_size"]))
    return f


def _ops_of(run, kind: str):
    """[(name, own seconds)] of the operations inside the traced programs
    of `kind`, and those programs' busy seconds (chip 0: one chip)."""
    t = run.get("trace")
    if t is None or not t.devices:
        return None, 0.0
    dev = t.devices[min(t.devices)]
    inside = sorted((s, s + d) for n, s, d in t._in_window(dev["modules"])
                    if PROGRAMS[kind].search(n))
    if not inside:
        return None, 0.0
    ops, j = [], 0
    for n, s, d in sorted(t._in_window(dev["ops"]), key=lambda e: e[1]):
        while j < len(inside) and inside[j][1] <= s:
            j += 1
        if j < len(inside) and inside[j][0] <= s and s + d <= inside[j][1]:
            ops.append((n, s, d))
    # a `while` or a call holds its body's operations: own time only
    own = [(n, ns / 1e9) for n, ns in self_times(ops)]
    return own, sum(sec for _n, sec in own)


def _hbm(run) -> float:
    return peaks.peak(run["device_kind"])["hbm_bytes_s"]


def _attrs(run, name: str, attr: str):
    """`attr` of the program's spans `name` that lie in the traced span on
    the harness's clock; None where no such span carries it."""
    got = spans.program_spans(run)
    if not got or run.get("trace_span") is None:
        return None
    a, b = run["trace_span"]
    if a is None:
        return None
    vals = [s["attrs"][attr] for s in got
            if s["name"] == name and attr in s["attrs"]
            and a <= s["start"] and s["end"] <= b]
    return vals or None


def ssm_device_share(run, ops, but=()):
    """Device time of the decode programs' operations that match any of
    the patterns `ops` and none of `but`, over those programs' busy
    time."""
    own, busy = _ops_of(run, "decode")
    if not own or not busy:
        return None
    secs = _seconds(own, ops, _fields(run), but)
    return 100.0 * secs / busy if secs else None


def ssm_state_roofline(run, ops, but=()):
    """Bytes the traced decode steps' one-step updates must move (the
    state and the taps of every live slot, the engine's `state_rows` on
    `engine.decode`, read once and written once in every Mamba layer) at
    the HBM peak, over the device time of the operations that touch the
    per-slot parts in the decode programs. Bound by bandwidth."""
    own, _busy = _ops_of(run, "decode")
    rows = _attrs(run, "engine.decode", "state_rows")
    if not own or not rows:
        return None
    f = _fields(run)
    secs = _seconds(own, ops, f, but)
    if not secs or not sum(rows):
        return None
    need = recurrent_counts.step_state_bytes(sum(rows), f)
    return 100.0 * need / _hbm(run) / secs


def ssm_scan_roofline(run, ops, but=()):
    """Bytes the traced prefills' scans must move (`scan_len` of every
    `engine.prefill` in the traced span: u and delta in and y out at E
    wide in the model's dtype, B and C, and a carry a prompt, in every
    Mamba layer) at the HBM peak, over the scans' device time in the
    prefill programs. A FLOOR: the scan is bound by the vector and
    transcendental units, not by HBM (lib/recurrent_counts.py)."""
    own, _busy = _ops_of(run, "prefill")
    lens = _attrs(run, "engine.prefill", "scan_len")
    if not own or not lens:
        return None
    f = _fields(run)
    secs = _seconds(own, ops, f, but)
    if not secs:
        return None
    need = recurrent_counts.scan_stream_bytes(sum(lens), f) \
        + recurrent_counts.scan_carry_bytes(len(lens), f)
    return 100.0 * need / _hbm(run) / secs


def paged_attn_mqa_roofline(run, ops, but=()):
    """Bytes the traced decode steps' paged attention must read (the
    shared K and V row of every live context token, once in every
    attention layer) at the HBM peak, over that attention's device time in
    the decode programs. The model's bytes, whatever pages the
    implementation copies. Bound by bandwidth."""
    own, _busy = _ops_of(run, "decode")
    if not own or "loop" not in run or run.get("trace_span") is None:
        return None
    f = _fields(run)
    secs = _seconds(own, ops, f, but)
    a, b = run["trace_span"]
    if a is None:
        return None
    ctx = sum(s[4] for s in run["loop"].steps if a <= s[0] and s[1] <= b)
    if not secs or not ctx:
        return None
    return 100.0 * recurrent_counts.mqa_read_bytes(ctx, f) / _hbm(run) / secs


def slot_state_bytes_per_slot(run):
    """The engine's gauge `paddle_tpu_serving_slot_state_bytes`, as the
    runner read it after building the engine, over the slots: bytes of
    recurrent state one slot holds."""
    total = run.get("slot_state_bytes")
    return None if total is None else total / run["slots"]

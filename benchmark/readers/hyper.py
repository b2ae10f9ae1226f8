"""Readers for a decoder whose residual is several streams mixed by
hyper-connections (configs/xing4_29b_a4b_serve.json; the program's steps
are `models/layers.py::hc_coefficients`, `hc_read`, `hc_write`, on a tuple
of `hc_mult` arrays). The device readers find the steps' operations by
signatures only they have, filled from the cell's sizes (the metric files
say which: an operation that names five or more arrays of one stream's
shape, one that takes phi or the `hc_columns` = hc_mult (hc_mult + 2)
coefficients, a tuple of float32 vectors of the tokens' length). They read
the same work whether XLA or a kernel does it, as long as the kernel's
call takes the streams. As
`readers/hybrid.py`'s, they look inside the programs of ONE kind
(`jit_prefill` or `jit_decode` on the line "XLA Modules"). The Sinkhorn
loop's turns are counted on the device too: the launches of one
instruction inside one `while`. Without a trace, or on a program that has
no such operation (the parent of the PR that added them), each returns
None.
"""
from __future__ import annotations

import collections
import re

from ..lib import hyper_counts, peaks
from ..lib.trace import self_times
from .hybrid import _seconds


def _fields(run) -> dict:
    cfg = run["config"]
    f = dict(cfg["sizes"])
    f.update(cfg.get("engine", {}))
    n = int(f["hc_mult"])
    f["stream_width"] = hyper_counts.stream_width(f)
    f["hc_columns"] = n * (n + 2)
    return f


def _events_inside(run, program):
    """[(name, start, nanoseconds)] of the operations inside the traced
    programs whose module name matches `program`, by start and an
    operation before those nested in it (chip 0: one chip)."""
    t = run.get("trace")
    if t is None or not t.devices:
        return []
    rx = re.compile(program)
    dev = t.devices[min(t.devices)]
    spans = sorted((s, s + d) for n, s, d in t._in_window(dev["modules"])
                   if rx.search(n))
    ops, j = [], 0
    for n, s, d in sorted(t._in_window(dev["ops"]),
                          key=lambda e: (e[1], -e[2])):
        while j < len(spans) and spans[j][1] <= s:
            j += 1
        if j < len(spans) and spans[j][0] <= s and s + d <= spans[j][1]:
            ops.append((n, s, d))
    return ops


def _ops_inside(run, program):
    """[(name, seconds)] of those operations and the programs' busy
    seconds."""
    ops = _events_inside(run, program)
    if not ops:
        return None, 0.0
    # a `while` or a call holds its body's operations: own time only
    own = [(n, ns / 1e9) for n, ns in self_times(ops)]
    return own, sum(sec for _n, sec in own)


def _hyper_seconds(run, program, ops, but):
    if not run["config"]["sizes"].get("hc_mult"):
        return None, 0.0
    own, busy = _ops_inside(run, program)
    if not own or not busy:
        return None, 0.0
    return _seconds(own, ops, _fields(run), but) or None, busy


def program_op_share(run, program, ops, but=()):
    """Device time of the operations inside the programs `program` that
    match any of the patterns `ops` and none of `but`, over those
    programs' busy time."""
    secs, busy = _hyper_seconds(run, program, ops, but)
    return 100.0 * secs / busy if secs else None


def hyper_mix_roofline(run, program, ops, but=()):
    """Bytes the stream's steps of the traced prefills must move (the
    prompt tokens the traced steps prefilled, each through every
    sub-layer: lib/hyper_counts.py::mix_bytes) at the HBM peak, over the
    device time of those steps' operations in the prefill programs. Bound
    by bandwidth: 2 n^2 + 2 n multiply-adds a number moved."""
    secs, _busy = _hyper_seconds(run, program, ops, but)
    if not secs or "loop" not in run:
        return None
    a, b = run["trace_span"]
    toks = sum(s[5] for s in run["loop"].steps if a <= s[0] and s[1] <= b)
    if not toks:
        return None
    need = hyper_counts.mix_bytes(toks, _fields(run))
    return 100.0 * need / peaks.peak(run["device_kind"])["hbm_bytes_s"] / secs


def hyper_sinkhorn_iters(run, program, loop):
    """Turns the Sinkhorn loops of the traced programs `program` ran, read
    off the device: every instruction of a loop's body is launched once a
    turn, so inside one `while` whose name matches `loop` the launches of
    one instruction (the name before ` = `) are its turns; the commonest
    count of a loop (an event on the loop's edge may fall outside), and
    the least over the loops, so that one cut short shows. None where no
    such loop ran: one stream, or a loop of one turn, which the compiler
    unrolls."""
    if not run["config"]["sizes"].get("hc_mult"):
        return None
    ops = _events_inside(run, program)
    rx = re.compile(loop.format(**_fields(run)))
    turns = []
    for i, (n, s, d) in enumerate(ops):
        if not rx.search(n):
            continue
        body, j = collections.Counter(), i + 1
        while j < len(ops) and ops[j][1] < s + d:
            if ops[j][1] + ops[j][2] <= s + d:
                body[ops[j][0].split(" = ")[0]] += 1
            j += 1
        if body:
            counts = collections.Counter(body.values())
            turns.append(max(counts, key=lambda c: (counts[c], c)))
    return min(turns) if turns else None

"""Readers of what the admission path says about itself (ISSUE 34): the
attributes `engine.prefill` and `engine.decode` have carried since PR 24
and PR 31 (`prompt_len`, `bucket`, `cached_tokens`; `active`, `ahead`), and
the two stamps inside `engine.build` and `engine.wait` (`filled`, `ready`),
each a reading of `TRACER.clock()` between the span's own two.

All of them read the spans that END in the run's window, from the ring
as `readers/spans.py::program_spans` maps it (`start` and `end` on the
harness's clock). A stamp is an attribute and stays on the tracer's clock:
`_offset` recovers the one offset `program_spans` gave the span's own two
stamps, so a tail (`end` less the stamp) is a difference on one clock. A
program without the attribute (the parent of the PR that added it), a
ring that dropped a span, or a window with no such span gives nothing to
read: each returns None.
"""
from __future__ import annotations

from ..lib import stats
from .spans import _steps, program_spans

PREFILL = "engine.prefill"


def _in_window(run, name):
    """The spans called `name` that ended in the window; None for a run
    that is not a serving run or whose ring has nothing to read."""
    if run.get("kind") != "serve":
        return None
    spans = program_spans(run)
    if not spans:
        return None
    lo, hi = run["window"]
    return [s for s in spans if s["name"] == name and lo < s["end"] <= hi]


def _offset(run):
    """What `program_spans` added to this run's spans, read back off one
    span the ring still holds; None if it holds none of them."""
    if "_span_offset" not in run:
        from paddle_tpu.observability import tracing
        mapped = {s["span_id"]: s["end"] for s in program_spans(run)}
        run["_span_offset"] = next(
            (mapped[s.span_id] - s.end for s in tracing.TRACER.spans()
             if s.end is not None and s.span_id in mapped), None)
    return run["_span_offset"]


def _after_stamp_ms_p50(run, spans, key):
    """Median milliseconds from the stamp `key` to the span's end; None
    if any of `spans` lacks the stamp, or there is none."""
    if not spans or any(key not in s["attrs"] for s in spans):
        return None
    off = _offset(run)
    if off is None:
        return None
    return stats.median([1e3 * (s["end"] - off - s["attrs"][key])
                         for s in spans])


def prefill_time_share(run):
    """Time inside `engine.prefill` over time inside `engine.step` (idle
    steps too: a step that only admits is all prefill), over the window.
    A step's first prefill is queued behind the decode in flight, so its
    span holds the rest of that decode too."""
    got = _steps(run, idle_too=True)
    if got is None:
        return None
    prefills = _in_window(run, PREFILL)
    stepped = sum(st["end"] - st["start"] for st, _ph in got[0])
    return 100.0 * sum(p["end"] - p["start"] for p in prefills) / stepped


def prefill_padding_share(run):
    """Of the prompt positions the window's prefill programs ran (their
    buckets), the share that was padding: no prompt token stood there."""
    prefills = _in_window(run, PREFILL)
    if not prefills:
        return None
    try:
        asked = sum(p["attrs"]["prompt_len"] - p["attrs"]["cached_tokens"]
                    for p in prefills)
        ran = sum(p["attrs"]["bucket"] for p in prefills)
    except KeyError:
        return None
    return 100.0 * (1.0 - asked / ran) if ran else None


def decode_ahead_share(run):
    """Of the window's dispatched decodes, the share dispatched while the
    one before was still unread: the device never drained. It is false on
    the first decode after an idle engine and on every step whose
    admission read a prefill's token."""
    decodes = [d for d in _in_window(run, "engine.decode") or ()
               if d["attrs"].get("active")]
    if not decodes or any("ahead" not in d["attrs"] for d in decodes):
        return None
    return 100.0 * sum(bool(d["attrs"]["ahead"]) for d in decodes) \
        / len(decodes)


def host_build_transfer_ms_p50(run):
    """Median over the window's decoding steps of `engine.build` from
    `filled` to its end: the batch's transfers to the device (the numpy
    fills lie before the stamp; `host_build_ms_p50` reads both)."""
    got = _steps(run)
    if got is None:
        return None
    builds = [ph for _st, phases in got[0] for ph in phases
              if ph["name"] == "engine.build"]
    return _after_stamp_ms_p50(run, builds, "filled")


def wait_readback_ms_p50(run):
    """Median over the window's `engine.wait` spans that had a decode to
    read (`of_step` set) of `ready` to the end: the copy of `[S]` tokens
    to the host once the device had them."""
    waits = [w for w in _in_window(run, "engine.wait") or ()
             if w["attrs"].get("of_step") is not None]
    return _after_stamp_ms_p50(run, waits, "ready")

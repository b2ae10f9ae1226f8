"""Readers of the reduced device trace (`lib/trace.py::Reduced`). Without
a trace each returns None. Operation names are matched by patterns built
from the cell's own sizes, so one metric file serves every configuration
whose kernel has that shape signature."""
from __future__ import annotations

from ..lib import peaks


def _trace(run, kind=None):
    if kind is not None and run.get("kind") != kind:
        return None
    return run.get("trace")


def _pattern(run, template: str) -> str:
    cfg, tr = run["config"], run["traffic"]
    fields = dict(cfg["sizes"])
    fields.update(cfg.get("engine", {}))
    fields.update({k: v for k, v in tr.items()
                   if isinstance(v, (int, float))})
    return template.format(**fields)


def idle_share(run, kind):
    t = _trace(run, kind)
    if t is None:
        return None
    share = t.idle_share()
    return None if share is None else 100.0 * share


def span_host_share(run, span):
    t = _trace(run)
    if t is None:
        return None
    share = t.span_host_share(span)
    return None if share is None else 100.0 * share


def program_ms(run, pattern):
    """Device time of one execution of the programs matching `pattern`
    (line "XLA Modules"), mean."""
    t = _trace(run)
    if t is None:
        return None
    n = t.op_count(pattern, "modules")
    return 1e3 * t.op_seconds(pattern, "modules") / n if n else None


def _traced_steps(run):
    loop = run["loop"]
    a, b = run["trace_span"]
    return [s for s in loop.steps if a <= s[0] and s[1] <= b]


def prefill_ms_per_ktok(run, pattern):
    """Device time of the prefill programs per 1,000 prompt tokens they
    took in, over the traced steps."""
    t = _trace(run)
    if t is None or "loop" not in run:
        return None
    toks = sum(s[5] for s in _traced_steps(run))
    return 1e6 * t.op_seconds(pattern, "modules") / toks if toks else None


def paged_attention_roofline(run, op):
    """Bytes the traced decode steps' paged-attention calls must read
    (K and V of every live context token, each layer) at the HBM peak,
    over the kernel's device time. Bound by bandwidth."""
    t = _trace(run)
    if t is None or "loop" not in run:
        return None
    secs = t.op_seconds(_pattern(run, op))
    if not secs:
        return None
    s = run["config"]["sizes"]
    ctx = sum(st[4] for st in _traced_steps(run))
    need = peaks.paged_attention_bytes(ctx, s["num_heads"], s["head_dim"]) \
        * s["num_layers"]
    return 100.0 * need / peaks.peak(run["device_kind"])["hbm_bytes_s"] / secs


def op_device_share(run, op, kind):
    """Device time of the matching operations over the device's busy
    time."""
    t = _trace(run, kind)
    if t is None or not t.busy_s:
        return None
    secs = t.op_seconds(_pattern(run, op))
    return 100.0 * secs / t.busy_s if secs else None


def flash_attention_roofline(run, op, units_per_call):
    """FLOPs the traced causal flash-attention calls must do at the bf16
    peak, over their device time, per chip. `op` captures the rows of each
    call from its output shape; `units_per_call` is the mean over the
    calls of one layer and step (lib/peaks.py: forward 2, dq 3, dk/dv 4;
    under remat the forward runs twice: 11 over 4 calls). Bound by
    compute."""
    t = _trace(run)
    if t is None or "step_seconds" not in run:
        return None
    calls = t.matching(_pattern(run, op))
    secs = sum(d for _c, _m, d in calls)
    if not secs:
        return None
    s, tr = run["config"]["sizes"], run["traffic"]
    flops = sum(peaks.causal_attention_call_flops(
        int(m.group(1)), int(tr["seq"]), s["head_dim"], units_per_call)
        for _c, m, _d in calls)
    return 100.0 * flops / peaks.peak(run["device_kind"])["flops_bf16"] / secs


def collective_exposed_share(run):
    t = _trace(run)
    if t is None or run.get("chips", 1) < 2:
        return None
    share = t.collective_exposed_share()
    return None if share is None else 100.0 * share

"""Readers for a decoder with multi-head latent attention (configs/
kanana2_30b_a3b_serve.json: ONE cached row [c | kr] a token a layer,
attention absorbed in decode). The kernel's share of the decode programs'
time is `readers/hybrid.py::decode_op_share` with this cell's pattern
(`layer_metrics/latent_attn_device_share.json`); what is here needs the
latent row's bytes or the engine's gauge. As that module's, the device
reader looks at the DECODE programs of the traced span only (a prefill
runs the other form of attention, in another kernel) and fills its pattern
from the cell's sizes (`hybrid._fields`: the runner hands them under the
names it indexes). Without a trace, or on a run that has no such operation
or counter, each returns None.
"""
from __future__ import annotations

from ..lib import latent_counts, peaks
from .hybrid import _decode_ops, _fields, _seconds


def paged_attn_latent_roofline(run, ops):
    """Bytes the traced decode steps' latent paged-attention calls must
    read (the row of every live context token, once a layer) at the HBM
    peak, over the kernel's device time in the decode programs. Bound by
    bandwidth (lib/latent_counts.py::latent_attn_flops says why)."""
    own, _busy = _decode_ops(run)
    if not own or "loop" not in run:
        return None
    f = _fields(run)
    secs = _seconds(own, ops, f)
    a, b = run["trace_span"]
    ctx = sum(s[4] for s in run["loop"].steps if a <= s[0] and s[1] <= b)
    if not secs or not ctx:
        return None
    need = latent_counts.paged_latent_bytes(ctx, f)
    return 100.0 * need / peaks.peak(run["device_kind"])["hbm_bytes_s"] / secs


def paged_bytes_per_token(run):
    """The engine's gauge `paddle_tpu_serving_paged_bytes_per_token`, as
    the runner read it after building the engine: bytes of the cache's
    paged parts one cached token holds."""
    return run.get("paged_bytes_per_token")

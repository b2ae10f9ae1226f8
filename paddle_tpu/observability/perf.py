"""Performance observability plane: cost registry, MFU, step attribution.

This module turns "MFU is 44%" into "these buckets/phases burn the gap".
Three pieces, all views over the one metrics registry:

* **cost registry** — every jitted callable we own (Executor programs,
  Engine per-bucket prefill/decode, fused-block ops) registers its
  ``lower().cost_analysis()`` FLOPs / bytes-accessed at trace time,
  keyed by ``(name, key)`` where ``key`` is the compile bucket or feed
  shape.  Exposed as ``paddle_tpu_perf_flops`` / ``paddle_tpu_perf_bytes``
  gauges.
* **step-time decomposition** — :class:`StepSampler` gates a sampled
  profile of one step in ``PADDLE_TPU_PERFWATCH_EVERY`` (default 50;
  0 disables).  On a sampled step the caller reports host / dispatch /
  device / transfer seconds via :func:`record_breakdown`: the executor
  fences phase boundaries with ``block_until_ready``, ``Engine.step``
  reads them off its own spans; between samples the hot path is
  untouched, so steady-state overhead stays ~0.
* **MFU accounting** — :func:`chip_peak_flops` resolves the chip's
  peak bf16 FLOP/s from ``jax.devices()[0].device_kind`` and
  :func:`mfu` converts achieved FLOP/s to model-flops-utilisation. A
  device kind that is not in the table (the CPU backend, a new chip)
  has NO peak: no MFU is reported there, rather than a number against
  a guessed chip.
"""
from __future__ import annotations

import logging
import math
import os
import threading
import time

from . import flight as _flight
from . import registry as _obs

__all__ = [
    "StepSampler",
    "analytic_gpt_flops",
    "chip_peak_bytes_per_s",
    "chip_peak_flops",
    "breakdowns",
    "costs",
    "drop_instance",
    "kernels",
    "kv_cache_gauge",
    "mfu",
    "mfu_gauge",
    "note_compile_seconds",
    "note_kernel",
    "record_breakdown",
    "register_cost",
    "register_jit_cost",
    "reset",
    "sampling_every",
    "set_every",
    "set_mfu",
]

logger = logging.getLogger("paddle_tpu.perf")

# ---------------------------------------------------------------------------
# Peak tables.
# ---------------------------------------------------------------------------

# (device_kind substring, peak bf16 FLOP/s).  Order matters: first match
# wins, so the more specific names come first.
_PEAKS = [
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5litepod", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]

# (device_kind substring, HBM bandwidth bytes/s).  Same shape as
# _PEAKS; override with TPU_PEAK_GBPS.
_BWS = [
    ("v6", 1640e9),
    ("v5p", 2765e9),
    ("v5 lite", 819e9),
    ("v5e", 819e9),
    ("v5litepod", 819e9),
    ("v5", 2765e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
]


def _device_kind() -> str:
    import jax

    return str(jax.devices()[0].device_kind)


def _peak(table, env_name: str, env_scale: float) -> tuple[float | None, str]:
    kind = _device_kind()
    env = os.environ.get(env_name)
    if env:
        try:
            return float(env) * env_scale, kind
        except ValueError:
            pass
    low = kind.lower()
    for sub, peak in table:
        if sub in low:
            return peak, kind
    return None, kind


def chip_peak_flops() -> tuple[float | None, str]:
    """(peak bf16 FLOP/s, device kind) for one chip; the peak is None
    for a device kind the table does not know.

    ``TPU_PEAK_TFLOPS_BF16`` overrides the table (e.g. for new chips or
    int8 serving).
    """
    return _peak(_PEAKS, "TPU_PEAK_TFLOPS_BF16", 1e12)


def chip_peak_bytes_per_s() -> tuple[float | None, str]:
    """(HBM bandwidth bytes/s or None, device kind); ``TPU_PEAK_GBPS``
    overrides."""
    return _peak(_BWS, "TPU_PEAK_GBPS", 1e9)


def mfu(flops: float, seconds: float) -> float:
    """Model-flops-utilisation of `flops` model FLOPs in `seconds`;
    0.0 where the device has no known peak (nothing to report)."""
    if seconds <= 0 or flops <= 0:
        return 0.0
    peak, _ = chip_peak_flops()
    return float(flops) / seconds / peak if peak else 0.0


def analytic_gpt_flops(cfg, tokens: int, ctx: int) -> float:
    """Matmul-only forward FLOPs for `tokens` new tokens of a GPT block
    stack at context length `ctx` — the fallback when XLA cost analysis
    is unavailable.  Same convention as benchmark/lib/peaks.py, which
    the training cells' `mfu` is made from (qkv+proj+mlp+attn matmuls +
    the LM head, no norms/softmax); tests/test_perf_plane.py holds the
    two together."""
    H = int(getattr(cfg, "hidden_size", 0))
    L = int(getattr(cfg, "num_layers", 0))
    F = int(getattr(cfg, "intermediate_size", 4 * H) or 4 * H)
    V = int(getattr(cfg, "vocab_size", 0))
    if not (H and L):
        return 0.0
    per_layer = (
        3 * 2 * H * H        # qkv projections
        + 2 * H * H          # output projection
        + 2 * 2 * ctx * H    # qk^T and attn@v
        + 2 * H * F + 2 * F * H  # mlp
    )
    return float(tokens) * (L * per_layer + 2 * H * V)


# ---------------------------------------------------------------------------
# Metric series (the ONE registration site for every paddle_tpu_perf_*
# name — check_metric_names.py holds this).
# ---------------------------------------------------------------------------

_FLOPS = _obs.gauge(
    "paddle_tpu_perf_flops",
    "XLA/analytic FLOPs per invocation of a jitted callable",
    ["name", "key"])
_BYTES = _obs.gauge(
    "paddle_tpu_perf_bytes",
    "XLA bytes accessed per invocation of a jitted callable",
    ["name", "key"])
_MFU = _obs.gauge(
    "paddle_tpu_perf_mfu",
    "live model-flops-utilisation (achieved/peak) per instrumented loop",
    ["name"])
_BREAKDOWN = _obs.gauge(
    "paddle_tpu_perf_step_breakdown_seconds",
    "last sampled step-time decomposition (host/dispatch/device/transfer)",
    ["name", "phase"])
# Compiles run 0.1s (tiny CPU programs) to minutes (big TPU models);
# the default request-latency buckets top out far too low.
_COMPILE_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                    30.0, 60.0, 120.0, 300.0)
_COMPILE_H = _obs.histogram(
    "paddle_tpu_perf_compile_seconds",
    "jit compile wall time per site (first-call wall clock)",
    ["site"], buckets=_COMPILE_BUCKETS)
_HBM = _obs.gauge(
    "paddle_tpu_perf_hbm_bytes",
    "device memory stats from jax (0 when the backend has none)",
    ["kind"])
_KV_BYTES = _obs.gauge(
    "paddle_tpu_perf_kv_cache_bytes",
    "bytes held by a serving engine's paged KV cache",
    ["engine"])


def _hbm_stat(stat: str) -> float:
    try:
        import jax

        st = jax.devices()[0].memory_stats()
        if st:
            return float(st.get(stat, 0) or 0)
    except Exception:
        pass
    return 0.0


_HBM.labels(kind="in_use").set_function(lambda: _hbm_stat("bytes_in_use"))
_HBM.labels(kind="limit").set_function(lambda: _hbm_stat("bytes_limit"))
_HBM.labels(kind="peak").set_function(lambda: _hbm_stat("peak_bytes_in_use"))


def kv_cache_gauge(engine_id: str):
    """Per-engine KV-cache-bytes gauge child (engine sets a weakref
    function on it; dropped with the engine's other series)."""
    return _KV_BYTES.labels(engine=engine_id)


def mfu_gauge(name: str):
    """Labeled MFU gauge child for `name` (callers may set_function)."""
    return _MFU.labels(name=name)


# ---------------------------------------------------------------------------
# Cost registry
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_COSTS: dict[tuple[str, str], dict] = {}
_BREAKDOWNS: dict[str, dict] = {}
_KERNELS: dict[str, dict] = {}


def costs_enabled() -> bool:
    return os.environ.get("PADDLE_TPU_PERFWATCH_COSTS", "1") != "0"


def register_cost(name: str, key: str, flops: float | None,
                  bytes_accessed: float | None = None,
                  source: str = "analytic") -> float | None:
    """Record the per-invocation cost of jitted callable (name, key)."""
    fl = float(flops) if flops and flops > 0 else None
    by = float(bytes_accessed) if bytes_accessed and bytes_accessed > 0 else None
    with _LOCK:
        _COSTS[(name, key)] = {"flops": fl, "bytes": by, "source": source}
    if fl is not None:
        _FLOPS.labels(name=name, key=key).set(fl)
    if by is not None:
        _BYTES.labels(name=name, key=key).set(by)
    return fl


def _cost_from_analysis(ca) -> tuple[float | None, float | None]:
    # jax returns a dict, a list of per-computation dicts, or None
    # depending on version/backend.
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None, None
    fl = ca.get("flops")
    by = ca.get("bytes accessed")
    fl = float(fl) if isinstance(fl, (int, float)) and fl > 0 else None
    by = float(by) if isinstance(by, (int, float)) and by > 0 else None
    return fl, by


def register_jit_cost(name: str, key: str, jitfn, *args,
                      analytic_flops: float | None = None) -> float | None:
    """Lower `jitfn(*args)` and register its XLA cost analysis.

    Lowering is abstract (shapes only — safe with donated buffers) but
    not free, so call this once per compile bucket, on the same path
    that pays the compile.  Falls back to `analytic_flops` when the
    backend reports nothing.  Never raises, but a failed lowering is
    logged with its traceback: for an engine bucket this is the FIRST
    trace of the program, and the error it hit is the one the real
    call is about to hit again.
    """
    fl = by = None
    if costs_enabled():
        try:
            fl, by = _cost_from_analysis(jitfn.lower(*args).cost_analysis())
        except Exception:
            logger.warning("lowering %s[%s] for cost analysis failed",
                           name, key, exc_info=True)
            fl = by = None
    if fl is not None:
        return register_cost(name, key, fl, by, source="xla")
    return register_cost(name, key, analytic_flops, by, source="analytic")


def costs() -> dict[tuple[str, str], dict]:
    with _LOCK:
        return {k: dict(v) for k, v in _COSTS.items()}


# ---------------------------------------------------------------------------
# Step sampling + breakdown
# ---------------------------------------------------------------------------

def _env_every() -> int:
    try:
        return max(0, int(os.environ.get("PADDLE_TPU_PERFWATCH_EVERY", "50")))
    except ValueError:
        return 50


_EVERY = _env_every()


def sampling_every() -> int:
    """Current sampling cadence (every Nth step; 0 = off)."""
    return _EVERY


def set_every(n: int) -> None:
    """Override the sampling cadence at runtime (tests)."""
    global _EVERY
    _EVERY = max(0, int(n))


class StepSampler:
    """Decides which steps pay for a fenced profile.

    ``tick()`` returns True on every Nth call where N is the *current*
    module cadence (so ``set_every`` toggles live samplers too).  The
    first tick never samples: step 1 is usually a compile.
    """

    __slots__ = ("name", "_n")

    def __init__(self, name: str):
        self.name = name
        self._n = 0

    def tick(self) -> bool:
        every = _EVERY
        if every <= 0:
            return False
        self._n += 1
        return self._n % every == 0


def record_breakdown(name: str, phases: dict[str, float]) -> None:
    """Report one sampled step's phase decomposition (seconds)."""
    now = time.time()
    with _LOCK:
        ent = _BREAKDOWNS.setdefault(name, {"samples": 0, "phases": {}})
        ent["samples"] += 1
        ent["time"] = now
        for ph, v in phases.items():
            ent["phases"][ph] = float(v)
    for ph, v in phases.items():
        _BREAKDOWN.labels(name=name, phase=ph).set(float(v))
    _flight.record("perf", "sample", name=name,
                   **{k: round(float(v), 6) for k, v in phases.items()})


def breakdowns() -> dict[str, dict]:
    with _LOCK:
        return {k: {"samples": v["samples"], "time": v.get("time"),
                    "phases": dict(v["phases"])}
                for k, v in _BREAKDOWNS.items()}


def set_mfu(name: str, value: float) -> None:
    """Set the live MFU gauge for `name` (explicit-update style; loops
    that prefer pull register a set_function on mfu_gauge instead)."""
    v = float(value)
    if not math.isfinite(v):
        v = 0.0
    _MFU.labels(name=name).set(v)


def note_compile_seconds(site: str, seconds: float) -> None:
    """Record one jit compile's wall time (first-call wall clock)."""
    _COMPILE_H.labels(site=site).observe(float(seconds))


# ---------------------------------------------------------------------------
# Kernel margins (autobench feeds this)
# ---------------------------------------------------------------------------

def note_kernel(key: str, winner: str, timings_ms: dict[str, float],
                errors: dict[str, str] | None = None,
                source: str = "measured") -> None:
    """Record an autobench decision: all measured candidate times, the
    winner, the winner's margin over the best loser (below 1 where the
    gate kept its default on a tie), the error of every candidate that
    failed to run, and whether it was measured in this process or
    adopted from the persistent tuning cache."""
    ts = {c: float(v) for c, v in timings_ms.items() if math.isfinite(v)}
    margin = None
    win_ms = ts.get(winner)
    losers = [v for c, v in ts.items() if c != winner]
    if win_ms and losers:
        margin = min(losers) / win_ms  # >1: winner is margin× faster
    with _LOCK:
        _KERNELS[key] = {"winner": winner, "candidates_ms": ts,
                         "margin": margin, "errors": dict(errors or {}),
                         "source": source}


def kernels() -> dict[str, dict]:
    with _LOCK:
        return {k: dict(v) for k, v in _KERNELS.items()}


def drop_instance(name: str, engine_id: str | None = None) -> None:
    """Drop the per-instance series for a garbage-collected owner."""
    _MFU.remove_matching(name=name)
    _BREAKDOWN.remove_matching(name=name)
    if engine_id is not None:
        _KV_BYTES.remove_matching(engine=engine_id)
    with _LOCK:
        _BREAKDOWNS.pop(name, None)


def reset() -> None:
    """Test hook: clear tables and per-(name,key) series."""
    with _LOCK:
        _COSTS.clear()
        _BREAKDOWNS.clear()
        _KERNELS.clear()
    for g in (_FLOPS, _BYTES):
        g.remove_matching()
    _MFU.remove_matching()
    _BREAKDOWN.remove_matching()


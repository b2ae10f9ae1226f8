"""Per-shape kernel auto-benchmark gate with a persistent tuning cache.

Motivation (VERDICT r5 weak #1): the hand-written Pallas flash-attention
kernel measured 0.756x vs stock XLA at BERT seq-512 shapes while the
model hot path still ran it — a hand kernel must EARN its slot per
shape, not hold it by construction. This module provides the gate:

  winner = prefer(key, {"pallas": fn_a, "xla": fn_b}, make_args)

On first call for `key` (a hashable shape/dtype signature) each
candidate is jitted, compiled and timed on freshly made concrete device
arrays; the winner is cached for the life of the process and every
later call for the same key returns instantly.

What the clock reads. One dispatched call with a sync costs about a
millisecond on the chip whatever the kernel does, so a sample is a
BATCH of back-to-back calls under one `block_until_ready`, sized to
last about `_SAMPLE_SECONDS`; a candidate's time is the median sample
divided by the batch, its spread the range of its samples. A floor
remains: one execution still costs 0.19 ms with one output buffer and
0.38 with two on the v5e host (chip run, PR 21), and candidates faster
than that read alike. (A `fori_loop` of calls inside one jit would pay the dispatch
once, but its inputs are loop-invariant: XLA:CPU was seen to hoist the
call out of the loop, through an `optimization_barrier` too, and a
perturbed input costs the Pallas side a pass the XLA side fuses away.)
So `default` holds the slot unless a challenger beats it by more than
the measured spread and by more than `_MIN_MARGIN`: a difference the
clock cannot resolve must not flip between two runs, because a flipped
kernel changes every program it sits in — different numbers run to
run, and a compile-cache miss for each. The gate times the candidate
alone, forward only; it does not see what XLA would have fused around
the reference.

The gate is invoked at trace time from inside jitted bodies
(models/gpt.py decoder_tail, the paged-attention scan body, a
shard_map stage of the trainer), where an ambient trace would turn
make_args() into tracers and the "timing" into a timing of tracing. So
the measuring round runs on a THREAD OF ITS OWN and refuses tracer
arguments: jax's trace state — the ambient trace, a shard_map's axis
environment, the context mesh — is thread-local, and a new thread
starts at top level. (``jax.ensure_compile_time_eval()`` is not
enough: it evaluates a Pallas kernel body's ``program_id`` eagerly
while jit traces the candidate, and every Pallas candidate then fails.
``jax.core.eval_context()`` leaves a shard_map's axis environment in
place, and with it every call of the jitted candidate misses jit's
fast path: inside the four-chip trainer's stages both flash-attention
candidates read 5.3-5.6 ms per call at a shape that takes under one
— chip run, PR 21.)
Python side effects during a jax trace run exactly once per
compilation, so the measurement cost is paid once per shape bucket,
never per step.

A candidate that raises (a kernel Mosaic refuses, an out-of-memory
shape) never wins, and its error is KEPT: `perf.kernels()` holds, per
key, the winner, every candidate's milliseconds and every candidate's
error (it is part of the perf snapshot); the same goes to the
persistent cache record and a warning on the module logger.

Persistent tuning cache (PR 7, TPP-style portable primitives): set
``PADDLE_TPU_AUTOBENCH_CACHE=/path/to/autobench.json`` and every
decision is also published to disk keyed by (shape key, device kind,
jax version, kernel schema version), so a *new process* — a restarted
trainer, or a fleet of serving replicas shipped a pre-warmed file —
skips in-process measuring entirely. Properties, mirroring the PR-4
checkpoint store:

  * atomic publish: records are merged into the current file content
    and committed by tmp + ``os.replace`` — a reader never sees a torn
    file, concurrent writers race benignly (last writer wins; the
    read-merge-write keeps disjoint keys from clobbering each other);
  * per-record CRC32 over the canonical JSON — a corrupt record is
    skipped (and re-measured), a corrupt FILE degrades to in-process
    measuring and is overwritten by the next publish;
  * version stamps: records carry the jax version and this module's
    ``KERNEL_VERSION``; a mismatch marks the record stale and it is
    re-measured (then re-published) rather than trusted.

CLI (fleet warm/inspect):  ``python -m paddle_tpu.ops.autobench
list|warm|invalidate`` — see ``_main`` below and docs/KERNELS.md.

Every decision is also recorded as structured telemetry
(paddle_tpu_autobench_* gauges + cache hit/miss/stale counters on the
process registry) and logged through the `paddle_tpu.autobench` logger.

Env knobs:
  PADDLE_TPU_AUTOBENCH=0          disable measuring; `default` wins
  PADDLE_TPU_AUTOBENCH_FORCE=name force a candidate (debug/A-B runs);
                                  a name no gate offers logs a warning
                                  (typo guard, like PADDLE_PS_FAULT_*)
  PADDLE_TPU_AUTOBENCH_CACHE=path persistent tuning-cache file
                                  (unset/empty/0 = in-process only)
  PADDLE_TPU_AUTOBENCH_VERBOSE=1  log-level switch: raises the
                                  `paddle_tpu.autobench` logger to INFO
                                  (with a stderr handler if the app
                                  configured none)
"""
from __future__ import annotations

import json
import logging
import os
import threading
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Callable

import jax

from ..observability import perf as _perf, registry as _obs

__all__ = ["prefer", "decisions", "clear", "stats",
           "register_warmer", "warm", "list_entries", "invalidate",
           "KERNEL_VERSION", "PRESETS"]

# Bump when any gated Pallas kernel's implementation changes materially:
# cached winners were measured against the OLD kernel and must not
# survive it. (The jax version is stamped independently.)
KERNEL_VERSION = 3

_FORMAT = "paddle-tpu-autobench-v1"

_CACHE: dict = {}
_LOCK = threading.Lock()
_DISK: dict | None = None      # (key_str, device) -> record, lazy-loaded
_DISK_PATH: str | None = None  # path _DISK was loaded from
_STATS = {"measures": 0, "candidate_errors": 0, "cache_hits": 0,
          "cache_misses": 0, "cache_stale": 0, "cache_corrupt": 0,
          "publishes": 0}
_WARNED_FORCE: set = set()

logger = logging.getLogger("paddle_tpu.autobench")

_CANDIDATE_MS = _obs.gauge(
    "paddle_tpu_autobench_candidate_ms",
    "measured median wall time per candidate per shape key",
    ["key", "candidate"])
_WINNER = _obs.gauge(
    "paddle_tpu_autobench_winner",
    "1 for the candidate holding the hot path of a shape key, else 0",
    ["key", "candidate"])
_CACHE_HITS = _obs.counter(
    "paddle_tpu_autobench_cache_hits_total",
    "decisions adopted from the persistent tuning cache (no measuring)")
_CACHE_MISSES = _obs.counter(
    "paddle_tpu_autobench_cache_misses_total",
    "lookups the persistent tuning cache had no record for")
_CACHE_STALE = _obs.counter(
    "paddle_tpu_autobench_cache_stale_total",
    "cache records ignored for a jax/kernel version mismatch")
_CACHE_CORRUPT = _obs.counter(
    "paddle_tpu_autobench_cache_corrupt_total",
    "cache files or records dropped for CRC/parse failures")
_MEASURES = _obs.counter(
    "paddle_tpu_autobench_measure_total",
    "in-process candidate measuring rounds (cold-path cost)")


def _verbose_logging():
    """PADDLE_TPU_AUTOBENCH_VERBOSE kept as a LOG-LEVEL switch: it used
    to print to stderr; now it raises the module logger to INFO (adding
    a stderr handler only when logging is unconfigured)."""
    if not os.environ.get("PADDLE_TPU_AUTOBENCH_VERBOSE"):
        return
    if logger.getEffectiveLevel() > logging.INFO:
        logger.setLevel(logging.INFO)
    if not logger.handlers and not logging.getLogger().handlers:
        h = logging.StreamHandler()
        h.setFormatter(logging.Formatter("[autobench] %(message)s"))
        logger.addHandler(h)


# one timing sample: back-to-back calls lasting about this long in all
# (at most _MAX_CALLS of them), so the ~1 ms a sync costs is a few
# percent of the sample and not the whole of it
_SAMPLE_SECONDS = 0.1
_MAX_CALLS = 100
# a challenger must beat `default` by this share of default's time (and
# by the measured spread) to take the slot
_MIN_MARGIN = 0.05


def _record_decision(key, winner: str, timings: dict[str, float],
                     errors: dict[str, str] | None = None,
                     source: str = "measured"):
    """`timings`: seconds per candidate that ran; `errors`: message per
    candidate that raised."""
    skey = str(key)
    errors = dict(errors or {})
    for name, t in timings.items():
        _CANDIDATE_MS.labels(key=skey, candidate=name).set(
            round(t * 1e3, 4))
    for name in (*timings, *errors):
        _WINNER.labels(key=skey, candidate=name).set(
            1.0 if name == winner else 0.0)
    ms = {k: round(v * 1e3, 4) for k, v in timings.items()}
    # the perf plane keeps the full per-candidate table so `top` can
    # show Pallas-vs-XLA margins, not just the winner name
    _perf.note_kernel(skey, winner, ms, errors, source)
    _verbose_logging()
    logger.info("%s -> %s %s (%s)", skey, winner, ms, source)
    for name, err in errors.items():
        logger.warning("%s: candidate %r failed and cannot win: %s",
                       skey, name, err)


def _measure(fn: Callable, make_args: Callable,
             reps: int) -> tuple[float, float]:
    """(median, spread) of the per-call wall time of `fn(*make_args())`
    jitted, over `reps` samples of back-to-back calls (module
    docstring), after one warm-up call that also pays compilation.
    Separated out so tests can inject deterministic timings."""
    args = make_args()
    if any(isinstance(a, jax.core.Tracer)
           for a in jax.tree_util.tree_leaves(args)):
        raise RuntimeError(
            "autobench candidates must be timed on concrete arrays; "
            "make_args() returned tracers (measuring under a trace "
            "would time the tracing)")
    jfn = jax.jit(fn)
    jax.block_until_ready(jfn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(jfn(*args))
    one = time.perf_counter() - t0
    calls = max(1, min(_MAX_CALLS, int(_SAMPLE_SECONDS / max(one, 1e-9))))
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = jfn(*args)
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) / calls)
    samples.sort()
    return samples[len(samples) // 2], samples[-1] - samples[0]


def _measure_all(key, candidates, make_args, reps):
    """One measuring round: (seconds, spread, error message) per
    candidate name."""
    timings, spreads, errors = {}, {}, {}
    cost_args = None
    for name, fn in candidates.items():
        try:
            timings[name], spreads[name] = _measure(fn, make_args, reps)
        except Exception as e:  # a candidate that errors never wins
            errors[name] = f"{type(e).__name__}: {e}"[:2000]
            continue
        # fused-block ops join the perf-plane cost registry on the same
        # once-per-key measuring path (roofline rows per candidate); a
        # failed cost observation must not void a successful timing
        if _perf.costs_enabled():
            try:
                if cost_args is None:
                    cost_args = make_args()
                _perf.register_jit_cost(f"ops:{name}", str(key),
                                        jax.jit(fn), *cost_args)
            except Exception:
                logger.warning("%s: no cost analysis for candidate %r",
                               key, name, exc_info=True)
    return timings, spreads, errors


def _pick(timings: dict, spreads: dict, default: str) -> str:
    """The fastest candidate — unless `default` ran and the fastest does
    not beat it by more than the clock resolves (module docstring)."""
    if not timings:
        return default
    best = min(timings, key=timings.get)
    if best == default or default not in timings:
        return best
    lead = timings[default] - timings[best]
    noise = max(spreads[default], spreads[best],
                _MIN_MARGIN * timings[default])
    return best if lead > noise else default


# ---------------------------------------------------------------------------
# persistent tuning cache
# ---------------------------------------------------------------------------

def cache_path() -> str | None:
    p = os.environ.get("PADDLE_TPU_AUTOBENCH_CACHE", "").strip()
    return p if p and p != "0" else None


def _device_kind() -> str:
    return str(jax.devices()[0].device_kind)


def _jax_version() -> str:
    return str(jax.__version__)


def _rec_crc(rec: dict) -> int:
    body = {k: v for k, v in rec.items() if k != "crc"}
    blob = json.dumps(body, sort_keys=True, separators=(",", ":"))
    return zlib.crc32(blob.encode("utf-8")) & 0xFFFFFFFF


def _read_file(path: str) -> dict:
    """(key_str, device) -> record from `path`. A corrupt file degrades
    to {} (in-process measuring still works); corrupt records are
    skipped individually. Both count toward the corrupt telemetry."""
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        records = doc["records"]
        assert isinstance(records, list)
    except FileNotFoundError:
        return {}
    except Exception as e:
        with _LOCK:
            _STATS["cache_corrupt"] += 1
        _CACHE_CORRUPT.inc()
        logger.warning("autobench cache %s unreadable (%s: %s) — "
                       "degrading to in-process measuring", path,
                       type(e).__name__, e)
        return {}
    out: dict = {}
    for rec in records:
        if not (isinstance(rec, dict) and "key" in rec and "device" in rec
                and "winner" in rec and rec.get("crc") == _rec_crc(rec)):
            with _LOCK:
                _STATS["cache_corrupt"] += 1
            _CACHE_CORRUPT.inc()
            continue
        out[(rec["key"], rec["device"])] = rec
    return out


def _disk_records() -> dict:
    """Lazy-load the cache file once per process (clear() resets)."""
    global _DISK, _DISK_PATH
    path = cache_path()
    if path is None:
        return {}
    with _LOCK:
        if _DISK is not None and _DISK_PATH == path:
            return _DISK
    recs = _read_file(path)
    with _LOCK:
        _DISK, _DISK_PATH = recs, path
    return recs


def _write_doc(path: str, records: dict):
    """Atomic, durable commit of the full record map: unique tmp file
    (pid+thread keyed — two in-process threads must not share one), an
    fsync so the rename never publishes a torn file, then os.replace."""
    doc = {"format": _FORMAT,
           "records": [records[k] for k in sorted(records)]}
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(
        d, f".{os.path.basename(path)}.tmp.{os.getpid()}."
           f"{threading.get_ident()}")
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=0, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


# serializes read-merge-write publishers within the process (two traced
# ops on different threads can decide at the same time); cross-process
# racers remain benign last-writer-wins via the fresh re-read
_PUBLISH_LOCK = threading.Lock()


def _publish(path: str, rec: dict):
    """Merge `rec` into the file atomically (read-merge-write, tmp +
    rename commit like the PR-4 chunk store). Concurrent publishers are
    last-writer-wins per key; the fresh re-read keeps disjoint keys."""
    rec = dict(rec)
    rec["crc"] = _rec_crc(rec)
    with _PUBLISH_LOCK:
        current = _read_file(path)
        current[(rec["key"], rec["device"])] = rec
        _write_doc(path, current)
    with _LOCK:
        _STATS["publishes"] += 1
        global _DISK, _DISK_PATH
        if _DISK_PATH == path and _DISK is not None:
            _DISK[(rec["key"], rec["device"])] = rec


def _disk_lookup(key, candidates) -> str | None:
    """Adoptable winner from the persistent cache, or None (counting a
    miss or a stale record as appropriate)."""
    if cache_path() is None:
        return None
    rec = _disk_records().get((str(key), _device_kind()))
    if rec is None:
        with _LOCK:
            _STATS["cache_misses"] += 1
        _CACHE_MISSES.inc()
        return None
    if (rec.get("jax") != _jax_version()
            or rec.get("kernels") != KERNEL_VERSION
            or rec["winner"] not in candidates):
        with _LOCK:
            _STATS["cache_stale"] += 1
        _CACHE_STALE.inc()
        logger.info("stale cache record for %s (jax %s/%s kernels %s/%s)"
                    " — remeasuring", key, rec.get("jax"), _jax_version(),
                    rec.get("kernels"), KERNEL_VERSION)
        return None
    with _LOCK:
        _STATS["cache_hits"] += 1
    _CACHE_HITS.inc()
    # null timing = the candidate errored when measured; its message
    # rides the record's "errors"
    timings = {n: float(t) / 1e3
               for n, t in (rec.get("timings_ms") or {}).items()
               if t is not None}
    _record_decision(key, rec["winner"], timings, rec.get("errors"),
                     source="cache")
    return rec["winner"]


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------

def prefer(key, candidates: dict[str, Callable], make_args: Callable,
           default: str | None = None, reps: int = 3) -> str:
    """Return the name of the candidate that holds `key`'s slot (the
    fastest; `default` on a tie the clock cannot resolve), measuring at
    most once per key per process — and, with
    PADDLE_TPU_AUTOBENCH_CACHE set, at most once per key per cache
    lifetime across processes.

    candidates: name -> nullary-composable fn taking make_args() outputs.
    make_args:  () -> tuple of concrete device arrays (built lazily, only
                on the measuring call).
    default:    winner when benchmarking is disabled, on a tie, and when
                every candidate raised (first name if None).
    """
    forced = os.environ.get("PADDLE_TPU_AUTOBENCH_FORCE")
    if forced:
        if forced in candidates:
            return forced
        # typo guard (PR-6 fault-knob idiom): a forced name no gate
        # offers would otherwise be silently ignored
        mark = (forced, tuple(sorted(candidates)))
        with _LOCK:
            fresh = mark not in _WARNED_FORCE
            _WARNED_FORCE.add(mark)
        if fresh:
            logger.warning(
                "PADDLE_TPU_AUTOBENCH_FORCE=%r names no candidate of "
                "this gate (candidates: %s) — ignoring the force and "
                "benchmarking normally", forced,
                ", ".join(sorted(candidates)))
    if default is None:
        default = next(iter(candidates))
    if os.environ.get("PADDLE_TPU_AUTOBENCH", "1") == "0":
        return default
    with _LOCK:
        hit = _CACHE.get(key)
    if hit is not None:
        return hit
    disk_winner = _disk_lookup(key, candidates)
    if disk_winner is not None:
        with _LOCK:
            return _CACHE.setdefault(key, disk_winner)
    with _LOCK:
        _STATS["measures"] += 1
    _MEASURES.inc()
    # on a thread of its own: top-level trace state whatever jit / scan /
    # shard_map trace this thread is in (see module docstring)
    with ThreadPoolExecutor(max_workers=1,
                            thread_name_prefix="autobench") as pool:
        timings, spreads, errors = pool.submit(
            _measure_all, key, candidates, make_args, reps).result()
    winner = _pick(timings, spreads, default)
    with _LOCK:
        _STATS["candidate_errors"] += len(errors)
        # a racing thread may have decided already; first one wins so the
        # process is consistent
        winner = _CACHE.setdefault(key, winner)
    _record_decision(key, winner, timings, errors)
    path = cache_path()
    if path is not None:
        try:
            _publish(path, {
                "key": str(key), "device": _device_kind(),
                "winner": winner, "jax": _jax_version(),
                "kernels": KERNEL_VERSION,
                "timings_ms": {n: (round(timings[n] * 1e3, 4)
                                   if n in timings else None)
                               for n in candidates},
                "errors": errors,
                "ts": round(time.time(), 3)})
        except OSError as e:  # unwritable cache never blocks the gate
            logger.warning("autobench cache publish to %s failed: %s",
                           path, e)
    return winner


def decisions() -> dict:
    """Snapshot of the cached key -> winner map (for /stats, tests)."""
    with _LOCK:
        return dict(_CACHE)


def stats() -> dict:
    """Process-local counters: measures, candidate_errors, cache_hits/
    misses/stale/corrupt, publishes (tests assert against these)."""
    with _LOCK:
        return dict(_STATS)


def clear():
    """Drop in-process decisions AND the loaded disk snapshot (the file
    itself is untouched; next prefer() re-reads it)."""
    global _DISK, _DISK_PATH
    with _LOCK:
        _CACHE.clear()
        _DISK, _DISK_PATH = None, None
        for k in _STATS:
            _STATS[k] = 0


# ---------------------------------------------------------------------------
# CLI surface: list / warm / invalidate (fleet pre-warm workflow)
# ---------------------------------------------------------------------------

_WARMERS: dict[str, Callable] = {}


def register_warmer(kernel: str, fn: Callable):
    """Register `fn(spec: dict) -> winner_name` for the warm CLI. Kernel
    modules register a spec-driven wrapper around their own gate so
    `warm` re-uses the exact keys/candidates the runtime will look up."""
    _WARMERS[kernel] = fn


def warm(specs: list[dict]) -> list[tuple[dict, str]]:
    """Run each spec's registered warmer (measuring + publishing through
    prefer())."""
    out = []
    for spec in specs:
        kind = spec.get("kernel")
        fn = _WARMERS.get(kind)
        if fn is None:
            raise KeyError(
                f"no warmer registered for kernel {kind!r} "
                f"(known: {', '.join(sorted(_WARMERS)) or 'none'})")
        out.append((spec, fn(dict(spec))))
    return out


# Model-shaped warm presets: the shapes the serving fleet / trainers
# actually hit (docs/KERNELS.md). dtype defaults to bfloat16 on TPU.
PRESETS: dict[str, list[dict]] = {
    "gpt_350m": [
        {"kernel": "flash_attention", "b": 8, "h": 16, "s": 1024,
         "d": 64, "causal": True},
        {"kernel": "fused_out_ln", "m": 8192, "din": 1024, "dout": 1024},
        {"kernel": "fused_ffn_block", "m": 8192, "h": 1024, "i": 4096,
         "act": "gelu_tanh", "norm": "none"},
        {"kernel": "fused_layer_norm", "rows": 8192, "cols": 1024},
    ],
    # what chip_smoke.py's serving phases reach: decoder_tail at the
    # decode batch and every prefill / prefill_tail bucket, and paged
    # attention at the decode batch and the two tail buckets
    "gpt_1p3b_serve": [
        *({"kernel": "fused_out_ln", "m": m, "din": 2048, "dout": 2048}
          for m in (8, 16, 64, 256, 512, 1024, 2048)),
        *({"kernel": "fused_ffn_block", "m": m, "h": 2048, "i": 8192,
           "act": "gelu_tanh", "norm": "none"}
          for m in (8, 16, 64, 256, 512, 1024, 2048)),
        *({"kernel": "paged_attention", "s": s, "h": 16, "d": 128,
           "p": 1025, "ps": 16, "m": 128} for s in (8, 16, 64)),
    ],
    "bert_base_512": [
        {"kernel": "flash_attention", "b": 16, "h": 12, "s": 512,
         "d": 64, "causal": False, "mask": True},
        {"kernel": "fused_out_ln", "m": 8192, "din": 768, "dout": 768},
        {"kernel": "fused_ffn_block", "m": 8192, "h": 768, "i": 3072,
         "act": "gelu", "norm": "post"},
        {"kernel": "fused_ffn", "m": 8192, "h": 768, "i": 3072},
        {"kernel": "fused_dropout_add_ln", "rows": 8192, "cols": 768},
        {"kernel": "fused_layer_norm", "rows": 8192, "cols": 768},
    ],
}


def list_entries(path: str | None = None) -> list[dict]:
    path = path or cache_path()
    if not path:
        return []
    return [dict(rec) for _k, rec in sorted(_read_file(path).items())]


def invalidate(path: str | None = None, match: str | None = None,
               stale_only: bool = False) -> int:
    """Remove cache records (all, by substring, or only version-stale
    ones). Returns the number removed; commit is atomic like publish."""
    path = path or cache_path()
    if not path:
        return 0
    removed = 0
    with _PUBLISH_LOCK:  # read under the lock: a concurrent in-process
        # publish between read and write must not be erased
        current = _read_file(path)
        keep = {}
        for k, rec in current.items():
            is_stale = (rec.get("jax") != _jax_version()
                        or rec.get("kernels") != KERNEL_VERSION)
            hit = (match in rec["key"]) if match is not None \
                else (is_stale if stale_only else True)
            if hit:
                removed += 1
            else:
                keep[k] = rec
        if removed:
            _write_doc(path, keep)
    if removed:
        global _DISK, _DISK_PATH
        with _LOCK:
            _DISK, _DISK_PATH = None, None
    return removed


def _import_warmer_modules():
    """Importing the kernel modules registers their warmers."""
    from . import flash_attention  # noqa: F401
    from . import paged_attention  # noqa: F401
    from . import pallas_block  # noqa: F401
    from . import pallas_ffn  # noqa: F401
    from . import pallas_fused_residual  # noqa: F401
    from . import pallas_layer_norm  # noqa: F401


def _main(argv: list[str]) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.ops.autobench",
        description="inspect/warm/invalidate the persistent kernel "
                    "tuning cache (docs/KERNELS.md)")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p_list = sub.add_parser("list", help="print cache records")
    p_list.add_argument("--path", default=None)
    p_list.add_argument("--json", action="store_true")
    p_warm = sub.add_parser(
        "warm", help="measure + publish decisions for model-shaped "
                     "presets or a JSON spec file")
    p_warm.add_argument("--path", default=None,
                        help="cache file (defaults to "
                             "PADDLE_TPU_AUTOBENCH_CACHE)")
    p_warm.add_argument("--preset", action="append", default=[],
                        choices=sorted(PRESETS))
    p_warm.add_argument("--specs", default=None,
                        help="JSON file: list of warm spec objects")
    p_inv = sub.add_parser("invalidate", help="remove cache records")
    p_inv.add_argument("--path", default=None)
    g = p_inv.add_mutually_exclusive_group(required=True)
    g.add_argument("--match", default=None,
                   help="remove records whose key contains this string")
    g.add_argument("--stale", action="store_true",
                   help="remove only version-stale records")
    g.add_argument("--all", action="store_true")
    ns = ap.parse_args(argv)

    if ns.cmd == "list":
        entries = list_entries(ns.path)
        if ns.json:
            print(json.dumps(entries, indent=2, sort_keys=True))
        else:
            if not entries:
                print("(no cache records)")
            for rec in entries:
                stale = (rec.get("jax") != _jax_version()
                         or rec.get("kernels") != KERNEL_VERSION)
                print(f"{rec['winner']:>8}  {rec['device']:<12} "
                      f"{'STALE ' if stale else ''}{rec['key']}")
        return 0
    if ns.cmd == "warm":
        if ns.path:
            os.environ["PADDLE_TPU_AUTOBENCH_CACHE"] = ns.path
        if not cache_path():
            print("no cache path: pass --path or set "
                  "PADDLE_TPU_AUTOBENCH_CACHE", file=__import__("sys").stderr)
            return 2
        _import_warmer_modules()
        specs: list[dict] = []
        for name in ns.preset:
            specs.extend(PRESETS[name])
        if ns.specs:
            with open(ns.specs, encoding="utf-8") as f:
                specs.extend(json.load(f))
        if not specs:
            print("nothing to warm: pass --preset and/or --specs",
                  file=__import__("sys").stderr)
            return 2
        for spec, winner in warm(specs):
            print(f"{winner:>8}  {spec}")
        s = stats()
        print(f"warmed {len(specs)} specs -> {cache_path()} "
              f"(measures={s['measures']} hits={s['cache_hits']})")
        return 0
    if ns.cmd == "invalidate":
        n = invalidate(ns.path, match=ns.match, stale_only=ns.stale)
        print(f"removed {n} records")
        return 0
    return 2  # pragma: no cover


if __name__ == "__main__":  # pragma: no cover - exercised via subprocess
    import sys

    # delegate to the CANONICAL module instance: under `python -m` this
    # file runs as `__main__`, but the kernel modules register their
    # warmers into `paddle_tpu.ops.autobench` — two module objects, two
    # _WARMERS dicts, so the CLI must drive the one the kernels see
    from paddle_tpu.ops import autobench as _canonical
    sys.exit(_canonical._main(sys.argv[1:]))

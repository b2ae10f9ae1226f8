"""Serving engine: continuous-batching decode loop over the cache of
parts (serving/model.py: K/V in pages, and whatever else the model keeps
per token, per slot or as a tally).

One Engine = one model + one preallocated page pool + one fixed-shape
slot batch. Each scheduler iteration (`step()`):

  1. expire deadlines (queued + running; preempted requests free ALL
     their pages back to the pool immediately);
  2. admit queued requests into free slots (capacity-gated FIFO),
     dispatch one jitted PREFILL per admission (prompt K/V -> pages,
     per-slot state -> the request's slot, first token) and read
     nothing back;
  3. dispatch ONE jitted DECODE over the whole slot batch (inactive
     slots ride along pointed at the trash page), then read the tokens
     of the decode dispatched the step BEFORE and the first tokens of
     this step's prefills, and record each slot's token, evicting on
     EOS / max_new_tokens. One decode is always in flight while the
     host admits, builds and emits: a running slot's input token is the
     previous decode's output, a freshly prefilled slot's its prefill's,
     both taken on the device.

Compilation contract: decode is one program per (slots, pages) bucket —
an Engine has exactly one such bucket, so one compile for its lifetime;
prefill compiles once per prompt-length bucket (page-aligned power-of-
two padding). `stats()["compiles"]` counts actual traces (the counter
increments inside the traced function, which only runs at trace time) —
tests assert at-most-one per bucket.

Threading: `submit()` may be called from any number of frontend threads
(bounded queue = backpressure); the step loop runs either on the
caller's thread (`run_until_idle`, deterministic tests) or on the
engine's own scheduler thread (`start()`).
"""
from __future__ import annotations

import itertools
import math
import os
import threading
import time
import weakref
from collections import defaultdict, deque
from typing import NamedTuple

import numpy as np

from ..distributed.fleet.runtime import fault_injection as _fi
from ..observability import (debug as _debug, flight as _flight,
                             meter as _meter, perf as _perf,
                             registry as _obs, tracing as _tracing,
                             watchdog as _watchdog)
from .kv_cache import PagePool, defrag_plan
from .model import DecodeModel
from .prefix_cache import PrefixCache
from .sampling import sample_tokens, seed_to_key
from .scheduler import QueueFull, Request, Scheduler

__all__ = ["Engine", "QueueFull"]

# engine telemetry (labeled per engine instance; the scheduler/pool
# series share the same label value). Hot-path writes are counter incs
# and histogram observes around the jitted calls — host-side
# microseconds against millisecond steps.
_REQS = _obs.counter(
    "paddle_tpu_serving_requests_total",
    "requests submitted to the engine", ["engine"])
_TOKENS = _obs.counter(
    "paddle_tpu_serving_tokens_total",
    "tokens generated (prefill first tokens + decode)", ["engine"],
    always=True)  # backs stats()["tokens_generated"]
_STEPS = _obs.counter(
    "paddle_tpu_serving_steps_total",
    "decode scheduler iterations that ran the slot batch", ["engine"],
    always=True)  # backs stats()["steps"]
_COMPILES = _obs.counter(
    "paddle_tpu_serving_compiles_total",
    "XLA trace events per program bucket (trace-time side effect)",
    ["engine", "bucket"])
_DECODE_H = _obs.histogram(
    "paddle_tpu_serving_decode_step_seconds",
    "length of one engine.decode span: this step's decode dispatched, "
    "then the wait for the tokens of the decode before and of this "
    "step's prefills", ["engine"])
_PREFILL_H = _obs.histogram(
    "paddle_tpu_serving_prefill_seconds",
    "length of one engine.prefill span: since ISSUE 44 the DISPATCH of "
    "a prefill (transfers and the jitted call returning, a compile "
    "where the bucket is new), not its device time: that is "
    "engine.wait up to `ready`, or jit_prefill in a device trace",
    ["engine"])
_LATENCY_H = _obs.histogram(
    "paddle_tpu_serving_request_latency_seconds",
    "submit-to-finish latency per request", ["engine"])
_QUEUE_DEPTH = _obs.gauge(
    "paddle_tpu_serving_queue_depth",
    "requests waiting for admission (live)", ["engine"])
_OCCUPANCY = _obs.gauge(
    "paddle_tpu_serving_page_occupancy",
    "fraction of KV pages in use (live)", ["engine"])
_SAMPLING_REQS = _obs.counter(
    "paddle_tpu_sampling_requests_total",
    "requests submitted with temperature > 0", ["engine"])
_SAMPLING_TOKENS = _obs.counter(
    "paddle_tpu_sampling_tokens_total",
    "tokens drawn from the Philox sampler (temperature > 0)",
    ["engine"])
_SLOT_STATE_BYTES = _obs.gauge(
    "paddle_tpu_serving_slot_state_bytes",
    "bytes of the cache's per-slot parts (state kept per sequence, not "
    "per token); 0 for a model that keeps none", ["engine"])
_PAGED_BYTES_PER_TOKEN = _obs.gauge(
    "paddle_tpu_serving_paged_bytes_per_token",
    "bytes of the cache's paged parts one cached token holds (every "
    "layer, and every pass of a model that loops)", ["engine"])

_RESIDUAL_STREAMS = _obs.gauge(
    "paddle_tpu_serving_residual_streams",
    "residual streams a token carries through the layers (1: a sum; n: "
    "hyper-connections over n streams)", ["engine"])

_engine_ids = itertools.count()


def _drop_engine_series(eid: str):
    for m in (_REQS, _TOKENS, _STEPS, _COMPILES, _DECODE_H, _PREFILL_H,
              _LATENCY_H, _QUEUE_DEPTH, _OCCUPANCY, _SAMPLING_REQS,
              _SAMPLING_TOKENS, _SLOT_STATE_BYTES, _PAGED_BYTES_PER_TOKEN,
              _RESIDUAL_STREAMS):
        m.remove_matching(engine=eid)


# `tokens` entry of a slot whose input the device holds and the host has
# not read: the decode before's output, or this step's prefill's
_FROM_DEVICE = -1


class _InFlight(NamedTuple):
    """A dispatched decode whose tokens the host has not read."""
    step: int           # `step` of the engine.step that dispatched it
    tokens: object      # [S] int32, on the device
    reqs: dict          # slot -> the request it makes a token for


def _bucket_len(n: int, page_size: int) -> int:
    """Smallest page-aligned power-of-two-pages length >= n."""
    pages = max(1, math.ceil(n / page_size))
    return page_size * (1 << (pages - 1).bit_length())


def _req_summary(req: Request, where: str) -> dict:
    """One request's postmortem line (JSON-safe, lock-free reads)."""
    return {"id": req.id, "where": where, "status": req.status,
            "trace_id": req.trace_id,
            "prompt_len": int(req.prompt.size),
            "generated": len(req.generated),
            "max_new_tokens": req.max_new_tokens, "slot": req.slot,
            "tier": req.priority, "tenant": req.tenant,
            "age_s": round(time.monotonic() - req.submitted_at, 3),
            "error": req.error}


class Engine:
    def __init__(self, model, num_slots: int = 8, num_pages: int = 64,
                 page_size: int = 16, max_seq_len: int | None = None,
                 eos_id: int | None = None, max_queue: int = 256,
                 prefix_cache_pages: int | None = None):
        import jax
        import jax.numpy as jnp

        if not isinstance(model, DecodeModel):
            raise TypeError(
                f"Engine serves a paddle_tpu.serving.DecodeModel; got "
                f"{type(model).__module__}.{type(model).__qualname__}")
        self.model = model
        self.eos_id = eos_id
        self.page_size = page_size
        self.num_pages = num_pages
        # the hard sequence ceiling is min(pool capacity, requested cap,
        # MODEL position limit) — without the model term a request could
        # decode past wpe and jnp.take would clip instead of erroring,
        # returning garbage tokens with status "done"
        cap = min(max_seq_len or num_pages * page_size,
                  num_pages * page_size, model.max_positions)
        # floor to a page multiple: prefill buckets are page-aligned and
        # must never pad past the model's position table
        if cap < page_size:
            raise ValueError(
                f"page_size {page_size} exceeds the sequence ceiling "
                f"{cap} (model/pool/max_seq_len)")
        self.max_seq_len = (cap // page_size) * page_size
        self.max_pages_per_req = max(
            1, min(num_pages, self.max_seq_len // page_size))
        self.num_slots = num_slots
        self.engine_id = f"e{next(_engine_ids)}"
        self.pool = PagePool(num_pages, page_size, inst=self.engine_id)
        self.scheduler = Scheduler(self.pool, num_slots, self.max_seq_len,
                                   max_queue=max_queue,
                                   inst=self.engine_id)
        if model.has_routing:
            # weakly: the engine owns the scheduler, not the other way
            keep = weakref.WeakMethod(self._keep_routing)
            self.scheduler.before_release = \
                lambda req, status: keep()(req, status)
        self.trash_page = num_pages      # paged parts carry P+1 pages
        # a model whose window layers keep a ring of pages a SLOT (a slot
        # part: nothing to allocate, nothing to pass): what the spans and
        # stats() say of it
        self.ring_pages = model.ring_pages(page_size) if model.window else 0
        self.cache = model.init_cache(num_pages, page_size, num_slots)
        # shared-prefix KV reuse (serving/prefix_cache.py): 0 pages =
        # disabled (the default — an idle engine then provably holds no
        # pages, the PR-2 invariant tests pin that)
        if prefix_cache_pages is None:
            prefix_cache_pages = int(os.environ.get(
                "PADDLE_TPU_PREFIX_CACHE_PAGES", "0") or 0)
        self.prefix_cache = None
        if prefix_cache_pages > 0 and not model.has_prefill_tail:
            raise ValueError(
                "prefix_cache_pages > 0 with a model that has no "
                "`prefill_tail`: a cached prefix resumes a prompt at a "
                "page boundary from the pages alone. A model that keeps "
                "per-slot state (a convolution's or a recurrence's last "
                "inputs) cannot have one: that state at the boundary is "
                "not in any page")
        if prefix_cache_pages > 0:
            self.prefix_cache = PrefixCache(
                self.pool, budget_pages=min(prefix_cache_pages,
                                            num_pages),
                inst=self.engine_id)
            self.scheduler.prefix_cache = self.prefix_cache

        self._compiles: dict[str, int] = defaultdict(int)
        self._latencies: deque[float] = deque(maxlen=4096)
        self._tok_window: deque[tuple[float, int]] = deque(maxlen=512)
        # registry series for this engine (stats() reads these back)
        eid = self.engine_id
        self._m_reqs = _REQS.labels(engine=eid)
        self._m_tokens = _TOKENS.labels(engine=eid)
        self._m_steps = _STEPS.labels(engine=eid)
        self._m_decode_h = _DECODE_H.labels(engine=eid)
        self._m_prefill_h = _PREFILL_H.labels(engine=eid)
        self._m_latency_h = _LATENCY_H.labels(engine=eid)
        self._m_sampling_reqs = _SAMPLING_REQS.labels(engine=eid)
        self._m_sampling_tokens = _SAMPLING_TOKENS.labels(engine=eid)
        # live gauges read through a weakref so the registry never pins
        # a dead engine (tests build hundreds per process)
        wr = weakref.ref(self)
        _QUEUE_DEPTH.labels(engine=eid).set_function(
            lambda: (lambda e: e.scheduler.queue_depth if e else 0.0)(
                wr()))
        _OCCUPANCY.labels(engine=eid).set_function(
            lambda: (lambda e: e.pool.occupancy if e else 0.0)(wr()))
        # a dead engine's series (incl. the weakref gauges, which would
        # otherwise report 0.0 forever) leave the exposition
        weakref.finalize(self, _drop_engine_series, eid)
        # postmortem wiring: a progress token (the engine must keep
        # producing tokens OR retiring requests while the scheduler is
        # non-idle — a wedged jitted call inside step() is exactly what
        # the watchdog exists to catch) and an in-flight-request
        # provider for debug bundles. Both probe through the weakref so
        # a dead engine unregisters itself; neither takes the step lock
        # (a wedged step HOLDS it). Decode steps alone are NOT the
        # probe: a healthy stream of requests that all finish at
        # prefill (max_new_tokens=1) or all fail/expire never runs a
        # decode step, so _wd_progress also advances on every token and
        # every request retirement.
        self._wd_progress = 0
        self._recent: deque[dict] = deque(maxlen=32)
        wd_name = f"serving.engine.{eid}"
        _watchdog.WATCHDOG.watch(
            wd_name,
            probe=lambda: (lambda e: None if e is None
                           else e._wd_progress)(wr()),
            idle=lambda: (lambda e: True if e is None
                          else e.scheduler.idle)(wr()))
        weakref.finalize(self, _watchdog.WATCHDOG.unwatch, wd_name)
        _debug.register_requests_provider(
            wd_name,
            lambda: (lambda e: None if e is None
                     else e._debug_requests())(wr()))
        weakref.finalize(self, _debug.unregister_requests_provider,
                         wd_name)
        self._lock = threading.Lock()    # step loop exclusivity
        self._step_no = 0                # `step` of the engine.step span
        self._stats_lock = threading.Lock()  # deque append vs snapshot
        # published-version identity (PR 12): stamped by warm_start
        # under the step lock, so ping/stats can never report a version
        # whose weights aren't the ones decoding. 0 = cold weights
        # (never warm-started from a published version)
        self.model_version = 0
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

        # donation halves cache HBM on device backends; CPU jit would
        # only warn about it
        donate = self._donate = jax.default_backend() != "cpu"
        S, M = num_slots, self.max_pages_per_req
        compiles = self._compiles

        def note_compile(bucket: str):
            # Python side effect inside the traced fn: runs once per
            # actual XLA trace, so this counts COMPILES, not steps
            compiles[bucket] += 1
            _COMPILES.labels(engine=eid, bucket=bucket).inc()
            _flight.record("serving", "compile", engine=eid,
                           bucket=bucket)

        # sampling params ride every program as slot-wide TRACED arrays
        # (sampling.py): a greedy slot (temperature 0) still takes the
        # literal argmax path inside sample_tokens, and no sampling
        # value can ever force a recompile — the one-compile-per-bucket
        # contract is pinned with sampling enabled
        # a prefill's first token stays on the device: the program writes
        # it at the request's slot of `feed`, the [S] vector the step's
        # decode takes as `prev_tokens` (and the host reads after it)
        def prefill(params, cache, tokens, true_len, page_row, slot, feed,
                    temps, topks, topps, seeds, steps):
            note_compile(f"prefill[{tokens.shape[0]}]")  # trace-time
            cache, logits = model.prefill(params, cache, tokens,
                                          true_len, page_row, slot)
            tok = sample_tokens(logits[None, :], temps, topks, topps,
                                seeds, steps)
            return cache, feed.at[slot].set(tok[0])

        def prefill_tail(params, cache, tokens, start, true_len,
                         page_row, slot, feed, temps, topks, topps, seeds,
                         steps):
            note_compile(f"prefill_tail[{tokens.shape[0]}]")
            cache, logits = model.prefill_tail(params, cache, tokens,
                                               start, true_len,
                                               page_row)
            tok = sample_tokens(logits[None, :], temps, topks, topps,
                                seeds, steps)
            return cache, feed.at[slot].set(tok[0])

        def decode(params, cache, tokens, prev_tokens, positions, tables,
                   temps, topks, topps, seeds, steps):
            note_compile(f"decode[slots={S},pages={M}]")  # trace-time
            # a slot whose token the host has not read (_FROM_DEVICE):
            # it is the decode before's output, or its prefill's
            tokens = jnp.where(tokens == _FROM_DEVICE, prev_tokens, tokens)
            cache, logits = model.decode(params, cache, tokens,
                                         positions, tables)
            return cache, sample_tokens(logits, temps, topks, topps,
                                        seeds, steps)

        kw = {"donate_argnums": (1,)} if donate else {}
        self._prefill = jax.jit(prefill, **kw)
        self._prefill_tail = jax.jit(prefill_tail, **kw)
        self._decode = jax.jit(decode, **kw)
        # the decode whose tokens the host has not read yet (None: none),
        # and what a decode with none before it takes as `prev_tokens`
        self._inflight: _InFlight | None = None
        self._no_tokens = jnp.zeros((S,), jnp.int32)
        self._decodes_ahead = 0
        self._tokens_discarded = 0

        # perf plane: per-bucket FLOP costs land in _register_perf_cost
        # on each bucket's first (compiling) call; a bounded window of
        # (time, flops) pairs backs the live MFU gauge the same way
        # _tok_window backs tokens_per_sec
        self.num_chips = 1               # single-chip engine today
        self._flops_window: deque[tuple[float, float]] = deque(maxlen=512)
        self._bucket_flops: dict[str, float] = {}
        self._perf_sampler = _perf.StepSampler(f"engine:{eid}")
        self._perf_name = f"engine:{eid}"
        _perf.mfu_gauge(self._perf_name).set_function(
            lambda: (lambda e: e.perf_rates()["mfu"] if e else 0.0)(wr()))
        _perf.kv_cache_gauge(eid).set_function(
            lambda: (lambda e: e._kv_cache_bytes()["paged"] if e else 0.0)(
                wr()))
        _SLOT_STATE_BYTES.labels(engine=eid).set_function(
            lambda: (lambda e: e._kv_cache_bytes()["slot"] if e else 0.0)(
                wr()))
        _PAGED_BYTES_PER_TOKEN.labels(engine=eid).set_function(
            lambda: (lambda e: e._kv_cache_bytes()["paged"]
                     / ((e.num_pages + 1) * e.page_size) if e else 0.0)(
                wr()))
        _RESIDUAL_STREAMS.labels(engine=eid).set(model.residual_streams)
        # the model's tallies as last read (stats() reports the change)
        self._tally_seen: dict = {}
        self._steps_seen = 0
        weakref.finalize(self, _perf.drop_instance, self._perf_name, eid)

    # -- submission (any thread) ---------------------------------------
    def submit(self, prompt, max_new_tokens: int = 16,
               deadline: float | None = None,
               eos_id: int | None = None, priority: int = 1,
               tenant: str = "default", temperature: float = 0.0,
               top_k: int = 0, top_p: float = 1.0,
               seed: int | None = None,
               return_routing: bool = False) -> Request:
        """Enqueue a request. `deadline` is RELATIVE seconds from now;
        raises QueueFull (backpressure) when the queue is at capacity
        and QuotaExceeded (a QueueFull) when `tenant` is over its
        token-bucket quota. `priority` is the admission tier
        (0 = highest; see scheduler.Scheduler). `temperature` 0 is
        greedy; > 0 samples via the replayable (seed, step) Philox
        stream (serving/sampling.py) — `seed` defaults to the request
        id, so an identical resubmission with an explicit seed (or the
        same wire id through the frontend) replays token-for-token.
        `return_routing` (a model with routed experts): when the request
        finishes, `req.routing` holds the experts chosen at each position
        it fed the model, [prompt + generated - 1, expert layers, k], read
        once from the cache's `routing` part (routing replay)."""
        if return_routing and not self.model.has_routing:
            raise ValueError("return_routing needs a model with routed "
                             "experts")
        req = Request(prompt, max_new_tokens,
                      deadline=None if deadline is None
                      else time.monotonic() + deadline,
                      eos_id=eos_id if eos_id is not None else self.eos_id,
                      priority=priority, tenant=tenant,
                      temperature=temperature, top_k=top_k, top_p=top_p,
                      seed=seed)
        req.return_routing = bool(return_routing)
        if req.temperature > 0:
            self._m_sampling_reqs.inc()
        # carry the caller's trace context (e.g. the frontend handler's
        # wire trace id) onto the request — minting a fresh id for
        # in-process callers, so EVERY request's flight timeline is
        # keyed by a trace id even without a wire hop
        req.trace_id = _tracing.TRACER.current_trace_id() \
            or _tracing.new_trace_id()
        # offered load is metered even if the scheduler rejects below —
        # billing sees what the tenant *sent*, not what was admitted
        _meter.METER.note_submitted(req.tenant, req.priority,
                                    int(req.prompt.size))
        self.scheduler.submit(req)
        self._m_reqs.inc()
        _flight.record("serving", "submit", trace_id=req.trace_id,
                       engine=self.engine_id, request=req.id,
                       prompt_len=int(req.prompt.size),
                       max_new_tokens=req.max_new_tokens,
                       tier=req.priority, tenant=req.tenant)
        self._wake.set()
        return req

    def generate(self, prompt, max_new_tokens: int = 16,
                 deadline: float | None = None,
                 timeout: float | None = 120.0, priority: int = 1,
                 tenant: str = "default", temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0,
                 seed: int | None = None) -> np.ndarray:
        """Blocking convenience: submit + wait (requires the scheduler
        thread running, or another thread driving step())."""
        return self.submit(prompt, max_new_tokens, deadline=deadline,
                           priority=priority, tenant=tenant,
                           temperature=temperature, top_k=top_k,
                           top_p=top_p, seed=seed).result(timeout)

    # -- checkpoint warm-start ------------------------------------------
    def warm_start(self, root: str, step: int | None = None,
                   version: int | None = None):
        """Swap in weights from a committed checkpoint manifest
        (paddle_tpu.checkpoint) without rebuilding the engine: shapes/
        dtypes must match the current model (the jitted programs and
        page pools are layout-anchored and stay valid).

        Two-phase so the swap is zero-downtime: the checkpoint read
        AND the host->device upload run off the step lock (decode
        keeps batching on the old weights through both), then the FLIP
        takes the lock for a single reference swap — weights change
        between steps, never inside one, and never with disk I/O or a
        device transfer under the step lock (the lock-blocking-call
        analysis rule pins the disk half). Models served here provide
        read_checkpoint/adopt_checkpoint (GPTDecodeModel does).

        ``version`` stamps the published-version identity the flip
        installs (online-learning hot swap): in-flight generations
        finish on the old weights' tokens-so-far, and every request
        prefilled after the flip — plus ping/stats — reports the new
        version. Defaults to ``step`` so a plain checkpoint warm start
        is still identifiable."""
        prepared = self.model.read_checkpoint(root, step=step)
        with self._lock:
            self.model.adopt_checkpoint(prepared)
            v = version if version is not None else step
            if v is not None:
                self.model_version = int(v)
        return self

    @classmethod
    def from_checkpoint(cls, root: str, step: int | None = None,
                        attn_impl: str | None = None,
                        **engine_kw) -> "Engine":
        """Build an Engine whose model (config + weights) comes from a
        checkpoint manifest — the serving cold-start path that skips
        re-initialising and re-uploading weights from scratch."""
        from .model import GPTDecodeModel
        model = GPTDecodeModel.from_checkpoint(root, step=step,
                                               attn_impl=attn_impl)
        return cls(model, **engine_kw)

    # -- step loop -----------------------------------------------------
    def _row(self, req: Request | None) -> list[int]:
        if req is None:
            return [self.trash_page] * self.max_pages_per_req
        return req.table.padded(self.max_pages_per_req,
                                fill=self.trash_page)

    def _req_sampling(self, req: Request):
        """Shape-[1] traced sampling args for the prefill programs."""
        seed = req.seed if req.seed is not None else req.id
        return (np.asarray([req.temperature], np.float32),
                np.asarray([req.top_k], np.int32),
                np.asarray([req.top_p], np.float32),
                seed_to_key(seed).reshape(1, 2),
                np.asarray([len(req.generated)], np.int32))

    def _apply_cow(self, req: Request):
        """Full-prompt bootstrap admission: copy the last matched page
        (the decode step will rewrite the last prompt position's KV
        there) into the request's private page, then drop the lookup
        ref the scheduler kept pinned for exactly this copy."""
        src, dst = req.prefix_cow
        self.cache = self.model.copy_pages(self.cache, [src], [dst])
        req.prefix_cow = None
        self.pool.free([src])
        if self.prefix_cache is not None:
            self.prefix_cache.note_cow()
        _flight.record("serving", "prefix_cow", trace_id=req.trace_id,
                       engine=self.engine_id, request=req.id,
                       src=src, dst=dst)

    def _cache_insert_prompt(self, req: Request):
        """Publish the freshly prefilled prompt's full pages (existing
        cached prefixes dedupe inside insert)."""
        if self.prefix_cache is None:
            return
        n = int(req.prompt.size) // self.page_size
        if n:
            self.prefix_cache.insert(req.prompt[:n * self.page_size],
                                     req.table.pages[:n])

    def _run_prefill(self, req: Request, feed):
        """Dispatch `req`'s prefill into its slot and read nothing: the
        program writes the first token at the slot's place in `feed`, the
        [S] device vector the step's decode takes its unread tokens from,
        and the new vector is returned. None for a bootstrap admission,
        which runs no program."""
        import jax.numpy as jnp
        if req.prefix_cow is not None:
            self._apply_cow(req)
        m = req.prefix_match
        if m is not None and m.full:
            # bootstrap: the WHOLE prompt was cached — no prefill at
            # all. The request enters the decode batch with no
            # generated tokens; the next decode step feeds the last
            # prompt token at position prompt_len-1 (re-deriving that
            # position's KV into the COW page, bit-identical in the
            # parity regime) and samples the first token there.
            _flight.record("serving", "prefill_skipped",
                           trace_id=req.trace_id, engine=self.engine_id,
                           request=req.id,
                           cached_tokens=m.tokens)
            return None
        start = m.tokens if m is not None else 0
        tail = req.prompt[start:] if start else req.prompt
        T = _bucket_len(tail.size, self.page_size)
        T = min(T, self.max_pages_per_req * self.page_size - start)
        toks = np.zeros((T,), np.int32)
        toks[:tail.size] = tail
        row = jnp.asarray(self._row(req), dtype=jnp.int32)
        samp = self._req_sampling(req)
        if start:
            bucket = f"prefill_tail[{T}]"
            fn = self._prefill_tail
            targs = (self.model.params, self.cache, jnp.asarray(toks),
                     np.int32(start), np.int32(tail.size), row,
                     np.int32(req.slot), feed, *samp)
        else:
            bucket = f"prefill[{T}]"
            fn = self._prefill
            targs = (self.model.params, self.cache, jnp.asarray(toks),
                     np.int32(tail.size), row, np.int32(req.slot), feed,
                     *samp)
        # read BEFORE the cost registration: lower() traces the fn and
        # seeds the jit cache, so the note_compile side effect fires
        # there, not on the timed first call
        pre_compiles = self._compiles.get(bucket, 0)
        if bucket not in self._compiles:
            # first call of this bucket pays the compile anyway; the
            # abstract lowering for cost analysis rides the same path
            self._register_perf_cost(bucket, fn, targs, T, start + T)
        # window layers: the prompt's positions their rings never hold
        window = self.model.window
        past = {"past_window": max(0, int(req.prompt.size) - window)} \
            if window else {}
        # a recurrence: the bucket its scan runs over, in how many chunks
        chunk = self.model.scan_chunk
        scan = {"scan_len": T, "scan_chunks": -(-T // min(chunk, T))} \
            if chunk else {}
        with _tracing.span("engine.prefill", trace_id=req.trace_id,
                           engine=self.engine_id, request=req.id,
                           prompt_len=int(req.prompt.size), bucket=T,
                           cached_tokens=start, slot=req.slot,
                           passes=self.model.passes,
                           **past, **scan,
                           **self._attn_form("prefill")) as sp:
            self.cache, feed = fn(*targs)
            compiled = self._compiles.get(bucket, 0) > pre_compiles
            if compiled:
                sp.attrs["compiled"] = True
        # the span's own stamps: one clock in the step. Since ISSUE 44 this
        # is the DISPATCH of the prefill (its device time is the step's
        # `engine.wait` up to `ready`, or `jit_prefill` in a device trace)
        dt = sp.end - sp.start
        self._m_prefill_h.observe(dt)
        if compiled:
            _perf.note_compile_seconds("engine.prefill", dt)
        self._note_flops(self._bucket_flops.get(bucket))
        # `seconds`: the dispatch, as the histogram (docs/DEBUGGING.md)
        _flight.record("serving", "prefill", trace_id=req.trace_id,
                       engine=self.engine_id, request=req.id,
                       bucket=T, seconds=round(dt, 6))
        self._cache_insert_prompt(req)
        return feed

    def _keep_routing(self, req: Request, status: str):
        """`Scheduler.before_release`: the routing of a flagged request,
        read from its pages before they are freed, however it ends (not
        after an error: the cache may be lost with it). The positions fed
        to the model are the prompt and all but the last token made."""
        if req.return_routing and req.generated and status != "error":
            req.routing = self.model.routing_of(
                self.cache, self._row(req),
                int(req.prompt.size) + len(req.generated) - 1)

    def step(self) -> bool:
        """One scheduler iteration; returns True if any work was done.

        The call is one `engine.step` span whose children are its phases
        in order, one after the other with nothing between them:
        `engine.admit` (deadlines, admission and the dispatch of the
        admitted requests' prefills: nothing is read there),
        `engine.build` (the numpy batch and its transfers),
        `engine.decode` (`engine.dispatch` of this step's decode, then
        `engine.wait` for the tokens of the decode dispatched the step
        before and the first tokens of this step's prefills) and
        `engine.emit` (metering and one `record_token` a token read). An
        engine with nothing queued and nothing running records nothing.

        Nothing the device makes is read before the step's decode is
        dispatched, so the device runs decode k-1, this step's prefills
        and decode k while the host admits, builds and emits: a slot that
        is still running takes its input token from decode k-1's output on
        the device, a freshly prefilled one from its prefill's, and a
        request's position, sampler counter and pages come from what has
        been dispatched for it. What a caller may assume: a request's
        first token is in `generated` (through `Scheduler.record_token`)
        when the call that admitted it returns, and a decode's token no
        later than the call after the one that dispatched it; a request
        that ends by `max_new_tokens` is left out of the decode after its
        last; one that ends any other way (EOS, cancel, deadline, error)
        while a decode holds its slot has that decode's token discarded,
        so `generated` never holds a token past the end; and
        `scheduler.idle` is False while a token is unread, so a loop that
        steps until idle has every token."""
        with self._lock:
            if self.scheduler.idle:
                return False
            self._step_no += 1
            with _tracing.span("engine.step", engine=self.engine_id,
                               step=self._step_no,
                               queue_depth=self.scheduler.queue_depth
                               ) as st:
                return self._step_phases(st)

    def _step_phases(self, st) -> bool:
        import jax
        import jax.numpy as jnp
        prev = self._inflight
        # what the device holds for this step's decode, a slot each: the
        # decode before's tokens, and over them the first token of each
        # prefill of this step, which `firsts` (slot -> request) names
        feed = self._no_tokens if prev is None else prev.tokens
        firsts: dict[int, Request] = {}
        with _tracing.span("engine.admit") as sp:
            for r in self.scheduler.expire_deadlines():
                self._note_done(r)
            admitted = self.scheduler.admit()
            sp.attrs["admitted"] = st.attrs["admitted"] = len(admitted)
            for req in admitted:
                if req.done():      # it went with a cache lost just below
                    continue
                try:
                    fed = self._run_prefill(req, feed)
                except Exception as e:
                    # a poison request fails ALONE: evict it with its
                    # pages, keep the engine serving everyone else
                    req.error = f"prefill failed: {type(e).__name__}: {e}"
                    self.scheduler.evict(req, "error")
                    self._note_done(req)
                    if self._recover_cache("failed prefill"):
                        # a donating backend lost the cache, and with it
                        # what was in flight: the decode before and this
                        # step's earlier prefills
                        self._tokens_discarded += len(firsts)
                        prev, feed, firsts = None, self._no_tokens, {}
                    continue
                if fed is not None:
                    feed, firsts[req.slot] = fed, req
            active = [(i, r) for i, r in enumerate(self.scheduler.slots)
                      if r is not None]
        st.attrs["active"] = len(active)
        if not active:
            # whatever is in flight decodes for requests that have ended
            self._discard_inflight()
            st.attrs["idle"] = True
            return bool(self.scheduler.queue_depth)
        with _tracing.span("engine.build") as build:
            sample = self._perf_sampler.tick()
            S = self.num_slots
            tokens = np.zeros((S,), np.int32)
            positions = np.zeros((S,), np.int32)
            tables = np.full((S, self.max_pages_per_req), self.trash_page,
                             np.int32)
            temps = np.zeros((S,), np.float32)
            topks = np.zeros((S,), np.int32)
            topps = np.ones((S,), np.float32)
            seeds = np.zeros((S, 2), np.uint32)
            steps = np.zeros((S,), np.int32)
            batch: dict[int, Request] = {}  # slot -> whom this decode serves
            pages_live = 0
            window = self.model.window
            ring_live = window_rows = 0
            for i, r in active:
                # tokens dispatched for r: those read, and the one the
                # device holds for its slot (the decode in flight's, or
                # its prefill's first)
                unread = (prev is not None and prev.reqs.get(i) is r) \
                    or firsts.get(i) is r
                n = len(r.generated) + unread
                last = int(r.prompt.size) + n - 1   # position of token n
                if window:  # (a finished request decodes nothing more)
                    done = n >= r.max_new_tokens
                    ring_live += min((last - done) // self.page_size + 1,
                                     self.ring_pages)
                    window_rows += 0 if done else min(last + 1, window)
                if n >= r.max_new_tokens:
                    # its last token is unread: nothing more to decode
                    pages_live += (last - 1) // self.page_size + 1
                    continue
                batch[i] = r
                # a bootstrap admission (whole prompt cached, prefill
                # skipped) reaches its first decode with NOTHING
                # generated: feed the last prompt token at position
                # prompt_len-1, exactly where prefill would have left it
                tokens[i] = _FROM_DEVICE if unread else (
                    r.generated[-1] if r.generated else int(r.prompt[-1]))
                positions[i] = last
                tables[i] = self._row(r)
                temps[i] = r.temperature
                topks[i] = r.top_k
                topps[i] = r.top_p
                seeds[i] = seed_to_key(r.seed if r.seed is not None
                                       else r.id)
                steps[i] = n
                # pages that hold a token once this step has written its own
                pages_live += last // self.page_size + 1
            # what the pool has handed out (worst case of every admitted
            # request) against what holds a token: ROADMAP Speed 5
            st.attrs["pages_reserved"] = self.pool.used_pages
            st.attrs["pages_live"] = pages_live
            if window:      # a slot's ring is its request's, whole
                st.attrs["window_pages_reserved"] = \
                    self.ring_pages * len(active)
                st.attrs["window_pages_live"] = ring_live
            bucket = f"decode[slots={S},pages={self.max_pages_per_req}]"
            # as in _run_prefill: read before lower() runs the trace
            pre_compiles = self._compiles.get(bucket, 0)
            # the numpy batch is made; what is left is its transfers
            build.attrs["filled"] = _tracing.TRACER.clock()
            if batch:
                targs = (self.model.params, self.cache, jnp.asarray(tokens),
                         feed, jnp.asarray(positions), jnp.asarray(tables),
                         jnp.asarray(temps), jnp.asarray(topks),
                         jnp.asarray(topps), jnp.asarray(seeds),
                         jnp.asarray(steps))
                if bucket not in self._compiles:
                    self._register_perf_cost(bucket, self._decode, targs,
                                             S, self.max_seq_len)
        # the device runs behind the host whenever something this engine
        # dispatched is unread: the decode before, or this step's prefills
        pending = prev is not None or bool(firsts)
        ahead = bool(batch) and pending
        next_toks = None
        try:
            with _tracing.span("engine.decode", engine=self.engine_id,
                               active=len(batch), ahead=ahead,
                               passes=self.model.passes,
                               **({"window_rows": window_rows}
                                  if window else {}),
                               # live slots whose recurrent state advances
                               **({"state_rows": len(batch)}
                                  if self.model.scan_chunk else {}),
                               **self._attn_form("decode")) as sp:
                with _tracing.span("engine.dispatch") as dispatch:
                    # with nothing left to decode, this step only reads
                    fl = None
                    if batch:
                        self.cache, device_toks = self._decode(*targs)
                        fl = _InFlight(self._step_no, device_toks, batch)
                        self._m_steps.inc()
                        self._decodes_ahead += ahead
                        self._note_flops(self._bucket_flops.get(bucket))
                    self._inflight = fl
                with _tracing.span(
                        "engine.wait",
                        of_step=None if prev is None else prev.step,
                        first_tokens=len(firsts)) as wait:
                    if prev is not None:
                        # hang injection (chaos drills): PADDLE_PS_FAULT_STALL
                        # with PADDLE_PS_FAULT_STALL_POINT=serving_decode
                        # wedges the step thread here, inside the step
                        # lock, where a hung decode would hold it (waiting
                        # for its tokens; the first tokens of the step that
                        # dispatched it are out), which is what the stall
                        # watchdog must catch while requests keep queueing
                        _fi.injector().maybe_stall("serving_decode")
                    if pending:
                        # the device finishing decode k-1 and, behind it,
                        # this step's prefills (decode k is queued behind
                        # them), then the copy of their tokens, one [S]
                        # vector: `ready` parts the two
                        jax.block_until_ready(feed)
                        wait.attrs["ready"] = _tracing.TRACER.clock()
                        next_toks = np.asarray(feed)
                compiled = self._compiles.get(bucket, 0) > pre_compiles
                if compiled:
                    sp.attrs["compiled"] = True
            dt = sp.end - sp.start
            self._m_decode_h.observe(dt)
        except Exception as e:
            # a decode-step failure poisons the whole slot batch, the
            # decode dispatched behind it and the prefills in front of it
            # whose tokens were not read (the cache buffer may be
            # donated/invalid): fail the in-flight requests with their
            # pages freed rather than wedging them
            for _i, r in active:
                r.error = f"decode failed: {type(e).__name__}: {e}"
                self.scheduler.evict(r, "error")
                self._note_done(r)
            self._tokens_discarded += len(firsts)
            self._discard_inflight()
            self._recover_cache("failed decode")
            raise
        with _tracing.span("engine.emit") as sp:
            if compiled:
                _perf.note_compile_seconds("engine.decode", dt)
            elif sample and prev is not None:
                # read off this step's spans: host = batch building;
                # dispatch = the async jit call returning; device = what
                # was left of the step before's decode (and of this
                # step's prefills) once this one was dispatched;
                # transfer = device->host copy
                ready = wait.attrs["ready"]
                _perf.record_breakdown(self._perf_name, {
                    "host": build.duration(),
                    "dispatch": dispatch.duration(),
                    "device": ready - wait.start,
                    "transfer": wait.end - ready,
                })
            finished = recorded = sampled_n = 0
            # decode k-1's tokens, then the first tokens: a slot in both
            # changed hands, and its old tenant's token goes
            for i, r in itertools.chain(
                    prev.reqs.items() if prev is not None else (),
                    firsts.items()):
                if self.scheduler.slots[i] is not r:
                    # it ended (EOS, cancel, deadline, error) while the
                    # program held its slot: the token is past its end
                    self._tokens_discarded += 1
                    continue
                recorded += 1
                sampled_n += r.temperature > 0
                if self.scheduler.record_token(r, int(next_toks[i])):
                    self._note_done(r)
                    finished += 1
            if recorded:
                self._note_tokens(recorded)
            if sampled_n:
                self._m_sampling_tokens.inc(sampled_n)
            sp.attrs["finished"] = finished
            self._forget_orphaned()
        return True

    def _discard_inflight(self):
        """Forget the decode in flight: its tokens are nobody's (its
        requests ended, or the cache it ran on is lost). Its K/V writes
        landed in pages its requests had reserved, before any program
        dispatched later can touch them."""
        if self._inflight is not None:
            self._tokens_discarded += len(self._inflight.reqs)
            self._inflight = None

    def _forget_orphaned(self):
        """Discard the decode in flight once every request of it has
        ended, so that an idle scheduler means nothing is unread."""
        fl = self._inflight
        if fl is not None and not any(self.scheduler.slots[i] is r
                                      for i, r in fl.reqs.items()):
            self._discard_inflight()

    def _recover_cache(self, why: str) -> bool:
        """After a failed jitted call on a DONATING backend the cache
        buffers may already be consumed — rebuild every part and fail
        whatever in-flight state they held; True if it did (CPU never
        donates: old cache stays valid, surviving requests keep
        decoding)."""
        if not self._donate:
            return False
        for r in list(self.scheduler.active_requests()):
            r.error = f"kv cache lost to a {why} (donated buffer)"
            self.scheduler.evict(r, "error")
            self._note_done(r)
        self._discard_inflight()
        self.cache = self.model.init_cache(self.num_pages, self.page_size,
                                           self.num_slots)
        self._tally_seen = {}
        return True

    def drain(self) -> "Engine":
        """Graceful removal from a serving fleet: stop admitting new
        requests (submit raises QueueFull("draining")), let everything
        queued or running finish. `stats()["draining"]` and the
        frontend's `ping` report it so a router stops routing here;
        `run_until_idle`/the scheduler thread empty the queue, then the
        process can exit with nothing lost."""
        self.scheduler.drain()
        self._wake.set()
        return self

    @property
    def draining(self) -> bool:
        return self.scheduler.draining

    def cancel(self, req: Request) -> bool:
        """Abandon a request (frontend timeout, client gone): dequeue or
        preempt it, freeing its pages. False if it already finished."""
        with self._lock:
            cancelled = self.scheduler.cancel(req)
            self._forget_orphaned()
            return cancelled

    def run_until_idle(self, max_steps: int = 100000):
        for _ in range(max_steps):
            self.step()
            if self.scheduler.idle:
                return
        raise RuntimeError(f"not idle after {max_steps} steps")

    def defrag(self):
        """Compact live pages to the low end of the pool (between steps).
        Shared pages move once; every holder — tables, the prefix
        cache's runs, and any pending COW source — is rewritten through
        the same mapping."""
        with self._lock:
            active = list(self.scheduler.active_requests())
            tables = [r.table for r in active]
            extra = self.prefix_cache.pages() if self.prefix_cache \
                else ()
            mapping = defrag_plan(self.pool, tables, extra_pages=extra)
            if self.prefix_cache is not None:
                self.prefix_cache.remap(mapping)
            for r in active:
                if r.prefix_cow is not None:
                    src, dst = r.prefix_cow
                    r.prefix_cow = (mapping.get(src, src),
                                    mapping.get(dst, dst))
            self.cache = self.model.apply_defrag(self.cache, mapping)
            return mapping

    # -- background thread ---------------------------------------------
    def start(self):
        if self._thread is not None:
            return self
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                try:
                    worked = self.step()
                except Exception:
                    # step() already failed the affected requests; the
                    # serving thread must survive a poison step or every
                    # later request wedges against a dead engine
                    import traceback
                    traceback.print_exc()
                    self._wake.wait(timeout=0.05)
                    self._wake.clear()
                    continue
                if not worked and self.scheduler.idle:
                    self._wake.wait(timeout=0.05)
                    self._wake.clear()

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="serving-engine")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- perf plane ----------------------------------------------------
    def _register_perf_cost(self, bucket: str, jitfn, targs,
                            tokens: int, ctx: int):
        """First call of a compile bucket: register its XLA FLOPs/bytes
        under (serving:<eid>, bucket), analytic matmul FLOPs as the
        fallback when the backend reports no cost analysis."""
        analytic = _perf.analytic_gpt_flops(
            self.model.cfg, tokens, ctx) or None
        fl = _perf.register_jit_cost(f"serving:{self.engine_id}", bucket,
                                     jitfn, *targs,
                                     analytic_flops=analytic)
        if fl:
            self._bucket_flops[bucket] = fl

    def _note_flops(self, flops: float | None):
        if flops:
            with self._stats_lock:
                self._flops_window.append((time.monotonic(), flops))

    def _attn_form(self, phase: str) -> dict:
        """Span attribute `attn` of a model whose prefill and decode run
        different forms of attention (`DecodeModel.attn_forms`), and
        `residual` of one whose residual is not a sum
        (`DecodeModel.residual_form`); nothing for the others."""
        form = self.model.attn_forms.get(phase)
        out = {"attn": form} if form else {}
        if self.model.residual_form:
            out["residual"] = self.model.residual_form
        return out

    def _kv_cache_bytes(self) -> dict:
        """Bytes of the cache by kind of part: {"paged", "slot",
        "tally"} (serving/model.py::DecodeModel)."""
        return {k: float(v)
                for k, v in self.model.cache_bytes(self.cache).items()}

    def _tally_stats(self) -> dict:
        """The model's tallies, read from the device under the step lock
        (the step donates the cache), and what `DecodeModel.tally_stats`
        makes of them and of their change since the last read. {} for a
        model without tallies, or while a step holds the lock for long."""
        names = self.model.parts_of("tally")
        if not names:
            return {}
        if not self._lock.acquire(timeout=2.0):
            return {}
        try:
            read = [np.asarray(self.cache[n]) for n in names]
            steps = int(self._m_steps.value)
        finally:
            self._lock.release()
        now = {n: a.astype(np.int64 if a.dtype.kind in "iu" else np.float64)
               for n, a in zip(names, read)}
        seen, self._tally_seen = self._tally_seen, now
        d_steps, self._steps_seen = steps - self._steps_seen, steps
        delta = {n: a - seen.get(n, 0) for n, a in now.items()}
        return self.model.tally_stats(now, delta, d_steps)

    def perf_rates(self) -> dict:
        """Cheap live rates for ping/stats and the perf snapshot: no
        latency sort, two deque copies under the stats lock."""
        with self._stats_lock:
            w = list(self._tok_window)
            fw = list(self._flops_window)
        tps = 0.0
        if len(w) >= 2 and w[-1][0] > w[0][0]:
            tps = sum(n for _, n in w[1:]) / (w[-1][0] - w[0][0])
        mfu = 0.0
        if len(fw) >= 2 and fw[-1][0] > fw[0][0]:
            flops_per_s = sum(f for _, f in fw[1:]) / (fw[-1][0] - fw[0][0])
            mfu = _perf.mfu(flops_per_s, 1.0)
        return {"tokens_per_sec": round(tps, 2),
                "tokens_per_s_per_chip": round(tps / self.num_chips, 2),
                "mfu": round(mfu, 5)}

    # -- stats ---------------------------------------------------------
    def _note_tokens(self, n: int):
        self._wd_progress += 1
        self._m_tokens.inc(n)
        with self._stats_lock:
            self._tok_window.append((time.monotonic(), n))

    def _req_flops(self, req: Request) -> float:
        """Metering-grade FLOPs estimate for one finished request from
        the compiled-cost registry: its prefill bucket's cost plus a
        per-token share of the decode bucket (a decode step's cost
        amortizes over the slot batch it ran with)."""
        if req.started_at is None:
            return 0.0          # never admitted — nothing executed
        T = _bucket_len(int(req.prompt.size), self.page_size)
        T = min(T, self.max_pages_per_req * self.page_size)
        total = self._bucket_flops.get(f"prefill[{T}]", 0.0)
        decode_toks = max(0, len(req.generated) - 1)
        if decode_toks:
            shares = []
            for bucket, fl in self._bucket_flops.items():
                if bucket.startswith("decode[slots="):
                    s = bucket[len("decode[slots="):].split(",", 1)[0]
                    try:
                        shares.append(fl / max(1, int(s)))
                    except ValueError:
                        pass
            if shares:
                total += decode_toks * (sum(shares) / len(shares))
        return total

    def _note_done(self, req: Request):
        self._wd_progress += 1
        lat = req.latency()
        if lat is not None:
            self._m_latency_h.observe(lat)
            with self._stats_lock:
                self._latencies.append(lat)
        _meter.METER.note_flops(req.tenant, req.priority,
                                self._req_flops(req))
        with self._stats_lock:
            self._recent.append(_req_summary(req, "finished"))

    # -- postmortem view (debug bundles / debug_dump verb) --------------
    def _debug_requests(self) -> dict:
        """JSON-safe in-flight table for postmortem bundles. Reads only
        the scheduler's queue lock (never the step lock — a wedged
        decode step holds that one, and this runs while it is stuck).
        Queue AND slots are read under that one lock, matching admit's
        dequeue+assign critical section, so no live request can fall
        between the two lists."""
        with self.scheduler._lock:
            queued = list(self.scheduler.queue)
            slotted = [(i, r) for i, r
                       in enumerate(self.scheduler.slots)
                       if r is not None]
        inflight = [_req_summary(r, "queued") for r in queued]
        inflight += [_req_summary(r, f"slot{i}") for i, r in slotted]
        with self._stats_lock:
            recent = list(self._recent)
        return {"engine": self.engine_id,
                "num_slots": self.num_slots,
                "queue_depth": len(queued),
                "inflight": inflight, "recent": recent}

    def stats(self) -> dict:
        """/stats counters: queue depth, latency percentiles, tokens/sec,
        page-pool occupancy, preemptions, compiles per bucket; and what the
        model makes of its tally parts (`DecodeModel.tally_stats`, its
        keys as they are), ratios over the time since the last call."""
        with self._stats_lock:  # the step thread appends concurrently
            lats = sorted(self._latencies)
            w = list(self._tok_window)
        total = int(self._m_tokens.value)

        def pct(p):
            if not lats:
                return None
            return round(lats[min(len(lats) - 1,
                                  int(p / 100 * len(lats)))] * 1e3, 3)

        tps = 0.0
        if len(w) >= 2 and w[-1][0] > w[0][0]:
            tps = sum(n for _, n in w[1:]) / (w[-1][0] - w[0][0])
        rates = self.perf_rates()
        pool = self.pool.stats()
        if self.model.window:
            pool["window"] = {"window": self.model.window,
                              "ring_pages": self.ring_pages}
        return {**self.scheduler.stats(), **self._tally_stats(),
                "pool": pool,
                "prefix_cache": self.prefix_cache.stats()
                if self.prefix_cache is not None else None,
                "model_version": self.model_version,
                "steps": int(self._m_steps.value),
                "decodes_ahead": self._decodes_ahead,
                "tokens_discarded": self._tokens_discarded,
                "tokens_generated": total,
                "tokens_per_sec": round(tps, 2),
                "tokens_per_s_per_chip": rates["tokens_per_s_per_chip"],
                "mfu": rates["mfu"],
                "latency_ms_p50": pct(50), "latency_ms_p99": pct(99),
                "completed_seen": len(lats),
                "compiles": dict(self._compiles)}

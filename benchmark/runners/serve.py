"""Runner of a served configuration: builds the program's Engine in this
process, owns its step loop, offers the traffic and times every token.

From the program it takes `GPTDecodeModel`, `Engine` (`submit`, `step`,
`stats`, `pool.stats`) and the requests' `generated` lists. One span of
its own is set at the scheduler's boundary: `Scheduler.record_token`, the
one place a token is produced, is wrapped on the instance so that every
token gets the harness's own clock when it exists (a step that prefills a
request and then decodes emits two of its tokens, a decode apart). Where
the wrap is bypassed, tokens are stamped when `step()` returns.
"""
from __future__ import annotations

import collections
import threading
import time

import numpy as np

from . import gpt_config
from ..lib import harness, stats, traffic as traffic_lib

SPAN = "bench.step"


class Track:
    """One request as the harness sees it."""
    __slots__ = ("req", "item", "due", "seen", "first_t", "last_t",
                 "primer", "client", "end_t")

    def __init__(self, req, item, due, primer, client):
        self.req, self.item, self.due = req, item, due
        self.seen, self.first_t, self.last_t = 0, None, None
        self.primer, self.client, self.end_t = primer, client, None


class Loop:
    """The step loop with its generator. One thread drives `step()`; an
    open loop adds one thread that sleeps until each arrival is due."""

    def __init__(self, eng, stream, tr: dict, annotate):
        self.eng, self.stream, self.tr = eng, stream, tr
        self.annotate = annotate
        self.clock = time.perf_counter
        self.live: dict[int, Track] = {}
        self.finished: list[Track] = []
        self.refused: list[dict] = []       # arrivals the engine refused
        self.gaps: list[tuple[float, float]] = []     # (stamp, gap seconds)
        self.ttft: list[tuple[float, float]] = []     # (due, seconds)
        self.late: list[tuple[float, float]] = []     # (due, sent - due)
        self.steps: list[tuple] = []        # (t0, t1, first, decoded, ctx, prompt tokens)
        self.pool_used: list[tuple[float, int]] = []
        self._stamps = collections.defaultdict(list)  # id(req) -> [t, ...]
        self._inbox = collections.deque()   # tracks sent by the thread
        self._stop = threading.Event()
        self._thread = None
        self.origin = None                  # open loop: stream's zero
        self._hook()

    def _hook(self):
        sched = self.eng.scheduler
        inner = sched.record_token
        stamps, clock = self._stamps, self.clock

        def record_token(req, token):
            stamps[id(req)].append(clock())
            return inner(req, token)
        sched.record_token = record_token

    # -- sending -----------------------------------------------------------
    def send(self, item, due, primer=False, client=None):
        now = self.clock()
        try:
            req = self.eng.submit(
                item["prompt"], item["max_new"], seed=item["seed"],
                temperature=item["temperature"], top_k=item["top_k"],
                top_p=item["top_p"])
        except Exception as e:              # QueueFull and the like
            self.refused.append({"due": due, "error": repr(e)})
            return None
        t = Track(req, item, due, primer, client)
        self.late.append((due, now - due))
        self._inbox.append(t)
        return t

    def _primer(self, item, i, k):
        """A request whose output is cut to the fraction (i+0.5)/k: the
        slots then start the window spread over their lengths, as a loop
        that has run for long has them."""
        item = dict(item)
        item["max_new"] = max(1, round(item["max_new"] * (i + 0.5) / k))
        return item

    def start(self):
        tr, now = self.tr, self.clock()
        k = int(tr.get("primers", 0))
        if tr["loop"] == "closed":
            for c in range(int(tr["clients"])):
                item = self.stream.next()
                if c < k:
                    item = self._primer(item, c, k)
                self.send(item, now, primer=c < k, client=c)
        else:
            for i in range(k):
                self.send(self._primer(self.stream.next(), i, k), now,
                          primer=True)
            self.origin = now
            self._thread = threading.Thread(target=self._arrivals,
                                            name="bench-arrivals")
            self._thread.start()

    def _arrivals(self):
        while not self._stop.is_set():
            item = self.stream.next()
            due = self.origin + item["due"]
            wait = due - self.clock()
            if wait > 0 and self._stop.wait(wait):
                return
            self.send(item, due)

    def stop_arrivals(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            if self._thread.is_alive():
                raise RuntimeError("arrival thread did not stop")
            self._thread = None

    # -- stepping ----------------------------------------------------------
    def step(self):
        while self._inbox:
            t = self._inbox.popleft()
            self.live[id(t.req)] = t
        t0 = self.clock()
        with self.annotate(SPAN):
            worked = self.eng.step()
        t1 = self.clock()
        n_first = n_new = ctx = prefilled = 0
        done = []
        for key, t in self.live.items():
            n = len(t.req.generated)
            if n > t.seen:
                marks = self._stamps.pop(key, ())
                for j in range(t.seen, n):
                    k = j - t.seen
                    at = marks[k] if len(marks) == n - t.seen else t1
                    if t.first_t is None:
                        t.first_t = at
                        self.ttft.append((t.due, at - t.due))
                        n_first += 1
                        prefilled += t.item["prompt_len"]
                    else:
                        self.gaps.append((at, at - t.last_t))
                    t.last_t = at
                n_new += n - t.seen
                # the decode read every token before the one it made
                ctx += t.item["prompt_len"] + n - 1
                t.seen = n
            if t.req.done():
                t.end_t = t1
                done.append(key)
        for key in done:
            t = self.live.pop(key)
            self._stamps.pop(key, None)
            self.finished.append(t)
            if t.client is not None and not self._stop.is_set():
                self.send(self.stream.next(), t1, client=t.client)
        if worked:
            # a request prefilled in this step has one token that no
            # decode made: its context is not a decode's read
            decoded = n_new - n_first
            self.steps.append((t0, t1, n_first, decoded, ctx, prefilled))
            self.pool_used.append((t1, self.eng.pool.stats()["used_pages"]))
        else:
            time.sleep(0.0005)
        return worked


def _engine(ctx):
    """The program under test, built as a user builds it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving import Engine, GPTDecodeModel

    sizes, ecfg = ctx.config["sizes"], ctx.config["engine"]
    dtype = ctx.config["dtype"]
    t0 = time.perf_counter()
    params = ctx.reference().make_weights(sizes, ctx.seed,
                                          jnp.dtype(dtype))
    jax.block_until_ready(params)
    ctx.say(f"weights: seed {ctx.seed}, {dtype}, on the device in "
            f"{time.perf_counter() - t0:.2f}s")
    gcfg = gpt_config(ctx.config)
    model = GPTDecodeModel(gcfg, params=params)
    eng = Engine(model, num_slots=ecfg["num_slots"],
                 num_pages=ecfg["num_pages"], page_size=ecfg["page_size"],
                 max_seq_len=ecfg["max_seq_len"],
                 max_queue=ecfg.get("max_queue", 256))
    return eng, params


def _warm_up(ctx, eng, stream_items, vocab):
    """Every prompt length of the mix once (the program chooses its own
    buckets, so the harness assumes none), two tokens each, slots full."""
    rng = np.random.Generator(np.random.Philox(key=[ctx.seed, 7]))
    lens = sorted({it["prompt_len"] for it in stream_items}, reverse=True)
    reqs = []
    for i, n in enumerate(lens):
        sampled = i % 2 == 1
        reqs.append(eng.submit(
            rng.integers(0, vocab, size=n, dtype=np.int32), 2, seed=i,
            temperature=0.8 if sampled else 0.0,
            top_p=0.9 if sampled else 1.0))
    eng.run_until_idle()
    bad = [r.status for r in reqs if r.status != "done"]
    if bad:
        raise RuntimeError(f"warm-up requests ended {bad}")
    return len(lens)


def run(ctx) -> dict:
    import jax

    tr = ctx.traffic
    sizes = ctx.config["sizes"]
    vocab = sizes["vocab_size"]
    eng, params = _engine(ctx)
    stream = traffic_lib.RequestStream(tr, vocab, ctx.seed)
    n_warm = _warm_up(ctx, eng, stream.items, vocab)
    compiles0 = dict(eng.stats()["compiles"])
    ctx.say(f"warm-up: {n_warm} prompt lengths; compiles {compiles0}; "
            f"set-up so far {ctx.setup_seconds():.2f}s")

    annotate = jax.profiler.TraceAnnotation
    loop = Loop(eng, stream, tr, annotate)
    ctx.freeze_gc()
    loop.start()
    ramp_end = loop.clock() + float(tr.get("ramp_s", 0.0))
    if tr["loop"] == "closed":
        # until every slot is taken
        while len(eng.scheduler.active_requests()) < eng.num_slots:
            loop.step()
    while loop.clock() < ramp_end:
        loop.step()

    # ---- the window -------------------------------------------------------
    lowered0 = ctx.lowerings
    t0 = loop.clock()
    setup_s = ctx.setup_seconds(t0)
    t_end = t0 + ctx.seconds
    t_trace = t_end - min(harness.TRACE_SECONDS, ctx.seconds)
    tracing, trace_start = False, None
    while loop.clock() < t_end:
        if ctx.trace and not tracing and loop.clock() >= t_trace:
            ctx.start_trace()
            tracing, trace_start = True, loop.clock()
        loop.step()
    lowered1 = ctx.lowerings
    # the window ends with the step in progress at its nominal end
    t_end = loop.steps[-1][1]
    trace_end = loop.clock()
    reduced = ctx.stop_trace() if tracing else None
    # ---- drain (open loop): every arrival of the window gets its first
    # token, or counts as beyond every percentile
    loop.stop_arrivals()
    drain_end = loop.clock() + float(tr.get("drain_s", 5.0))
    while tr["loop"] == "open" and loop.clock() < drain_end and (
            loop._inbox or any(t.first_t is None and t0 <= t.due < t_end
                               for t in loop.live.values())):
        loop.step()

    compiles1 = dict(eng.stats()["compiles"])
    est = eng.stats()
    gate = harness.gate_decisions()
    mem_peak = harness.memory_peak_bytes(jax, 1)
    in_win = lambda t: t0 <= t <= t_end
    window_s = t_end - t0

    # ---- end-to-end ---------------------------------------------------------
    steps_in = [s for s in loop.steps if t0 < s[1] <= t_end]
    n_tokens = sum(s[2] + s[3] for s in steps_in)
    rates = stats.slice_rates([(s[1], s[2] + s[3]) for s in steps_in], t0,
                              ctx.seconds)
    gaps_ms = [g * 1e3 for at, g in loop.gaps if in_win(at)]
    open_loop = tr["loop"] == "open"
    refused_in = sum(1 for r in loop.refused if in_win(r["due"]))
    done_in = [t for t in loop.finished
               if t.end_t is not None and in_win(t.end_t)]
    wrong = [t for t in done_in if t.req.status != "done"
             or len(t.req.generated) != t.item["max_new"]]
    failed = len(wrong) + refused_in
    if open_loop:
        # an arrival of the window with no first token by the end of the
        # drain, or refused, lies beyond every percentile
        due_in = [t for t in [*loop.live.values(), *loop.finished,
                              *loop._inbox]
                  if in_win(t.due) and not t.primer]
        ttft_ms = [(t.first_t - t.due) * 1e3 if t.first_t is not None
                   else float("inf") for t in due_in]
        failed += sum(1 for x in ttft_ms if x == float("inf"))
        ttft_ms += [float("inf")] * refused_in
        attempted = len(due_in) + refused_in
    else:
        ttft_ms = [s * 1e3 for due, s in loop.ttft if in_win(due)]
        attempted = len(done_in) + refused_in
    e2e = {
        # all the tokens of the window over all its time
        "out_tok_s": n_tokens / window_s,
        # one sample per gap between two tokens of one stream
        "itl_p95_ms": stats.percentile(gaps_ms, 95),
        "setup_s": setup_s,
    }
    ctx.say(f"window {window_s:.3f}s: {n_tokens} tokens, "
            f"{e2e['out_tok_s']:.2f} tok/s, median slice "
            f"{stats.median(rates):.2f}; slices "
            f"{' '.join(f'{r:.1f}' for r in rates)}")
    ctx.say("gap percentiles ms: " + " ".join(
        f"p{p}={stats.percentile(gaps_ms, p):.3f}"
        for p in (50, 90, 95, 97, 99)) + f" max={max(gaps_ms):.3f} "
        f"mean={sum(gaps_ms) / len(gaps_ms):.3f} n={len(gaps_ms)}")
    fin = [x for x in ttft_ms if x != float("inf")]
    ctx.say("ttft percentiles ms: " + " ".join(
        f"p{p}={stats.percentile(fin, p):.3f}"
        for p in (50, 75, 90, 95)) + f" n={len(fin)}")
    third = ctx.seconds / 3
    for lo, hi in ((t0, t0 + third), (t_end - third, t_end)):
        part = [s * 1e3 for due, s in loop.ttft if lo <= due < hi]
        ctx.say(f"ttft of arrivals due {lo - t0:.0f}-{hi - t0:.0f}s: n="
                f"{len(part)} p50={stats.percentile(part, 50)} "
                f"p90={stats.percentile(part, 90)}")
    ctx.say(f"queue depth at the window's end: {est['queue_depth']}, "
            f"active slots {est['active_slots']}")
    ctx.say(f"requests: attempted {attempted}, finished in window "
            f"{len(done_in)}, failed {failed}; gaps {len(gaps_ms)}, first "
            f"tokens {len(ttft_ms)}; steps {len(loop.steps)}; engine "
            f"completed={est['completed']} rejected={est['rejected']} "
            f"preemptions={est['preemptions']}")

    # ---- correct ------------------------------------------------------------
    ctx.check("requests that ended wrong or were refused", failed, 0)
    ctx.check("programs compiled inside the window",
              sum(compiles1.values()) - sum(compiles0.values()), 0)
    ctx.check("programs lowered inside the window", lowered1 - lowered0, 0)
    ctx.say(f"compiles before {compiles0} after {compiles1}")
    # free the program's cache before the reference runs
    del eng.cache
    ctx.release()
    sample = _sample(ctx, done_in)
    ctx.check("finished greedy requests to compare", -len(sample), -1)
    _compare(ctx, params, sample)

    return {"end_to_end": e2e, "attempted": attempted, "failed": failed,
            "memory_peak_bytes": mem_peak, "gate": gate, "trace": reduced,
            "loop": loop, "window": (t0, t_end), "slice_rates": rates, "ttft_ms": ttft_ms,
            "trace_span": (trace_start, trace_end),
            "slots": ctx.config["engine"]["num_slots"],
            "pool_pages": ctx.config["engine"]["num_pages"],
            "config": ctx.config, "traffic": tr,
            "device_kind": jax.devices()[0].device_kind,
            "kind": "serve"}


def _sample(ctx, done_in):
    """A seeded sample of the greedy requests finished in the window, the
    longest always in it."""
    greedy = [t for t in done_in if t.item["temperature"] == 0.0
              and t.req.status == "done"]
    if not greedy:
        return []
    k = int(ctx.config["correct"]["sample_requests"])
    greedy.sort(key=lambda t: t.item["index"])
    longest = max(greedy, key=lambda t: t.item["prompt_len"]
                  + len(t.req.generated))
    rest = [t for t in greedy if t is not longest]
    rng = np.random.Generator(np.random.Philox(key=[ctx.seed, 11]))
    picks = [rest[i] for i in rng.permutation(len(rest))[:k - 1]]
    return [longest] + picks


def served_gaps(ctx, params, sample, precision="f32", alt_precision=None):
    """For each sampled request, the reference run once over its prompt
    with its served tokens. Returns (widest gap of a served token below
    the reference's best logit, served tokens compared, and, with
    `alt_precision`, the widest gap of the token that precision puts
    first at the same positions: the control)."""
    ref = ctx.reference()
    sizes = ctx.config["sizes"]
    T = int(ctx.config["correct"]["reference_length"])
    worst, worst_alt, n = 0.0, 0.0, 0
    for t in sample:
        p, g = t.item["prompt_len"], len(t.req.generated)
        ids = np.zeros((T,), np.int32)
        ids[:p] = t.item["prompt"]
        ids[p:p + g] = t.req.generated
        alt = None
        if alt_precision:
            _, _, best = ref.next_token_gaps(params, ids, sizes,
                                             alt_precision)
            alt = np.concatenate([ids[:1], np.asarray(best)])
        gap, gap_alt, _ = ref.next_token_gaps(params, ids, sizes, precision,
                                              alt)
        # position p-1 predicts the first served token, p+g-2 the last
        worst = max(worst, float(np.max(np.asarray(gap)[p - 1:p + g - 1])))
        worst_alt = max(worst_alt, float(np.max(
            np.asarray(gap_alt)[p - 1:p + g - 1])))
        n += g
    return worst, n, worst_alt


def _compare(ctx, params, sample):
    if not sample:
        return
    t0 = time.perf_counter()
    worst, n, ctrl = served_gaps(ctx, params, sample,
                                 alt_precision=ctx.control)
    ctx.say(f"reference: {len(sample)} requests, {n} served tokens, "
            f"{time.perf_counter() - t0:.1f}s")
    if ctx.control:
        ctx.control_readings["gap"] = ctrl
        ctx.say(f"CONTROL {ctx.control}: widest gap of the token it puts "
                f"first below the reference's best logit: {ctrl!r} "
                f"(program: {worst!r})")
    ctx.check("widest gap of a served greedy token below the reference's "
              "best logit", worst, float(ctx.config["correct"]["gap_limit"]))

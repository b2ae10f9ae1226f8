#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py              one TPU chip: serve gpt_1p3b, train gpt_350m
    python3 chip_smoke.py --chips 4    four chips: the trainer over a pp=2 x tp=2 mesh
    python3 chip_smoke.py --rehearse   the same code at GPTConfig.tiny() widths on
                                       the CPU, Pallas in interpret mode. A
                                       rehearsal is never a pass.

One process drives every chip it is given. It drives the main paths once,
through the entry points a user calls, at the full width of the models
(counts are cut, never widths; weights are random from a seed):

  logits   GPTDecodeModel.prefill + decode (+ prefill_tail) against a float32
           models.gpt.gpt_forward over the whole sequence, for the XLA paged
           path and for the Pallas one — outside the engine and any timing
  serve    Engine -> ServingServer on 127.0.0.1:0 -> ServingClient, 12 mixed
           requests over 8 slots, twice; one compile per bucket, none on the
           second pass; one greedy request alone, in the mix and over the wire
  prefix   a second engine with the prefix cache on: a shared 256-token prefix,
           short tails, a whole-prompt hit (prefill_tail and copy-on-write)
  train    HybridParallelTrainStep, gpt_350m at the widths of
           benchmark/configs/gpt_350m_train.json, batch 8 x 1024, four steps
           on one batch: finite, falling loss, and the attention asked for is
           the attention that ran
  kernels  the gate's decisions (key, winner, ms and error per candidate) and
           every pallas_call built, none of them in interpret mode on the chip

Without a TPU (and without --rehearse) it exits non-zero with one line saying
what jax found and prints no result. The last line of standard output of a run
on the chip is one JSON object: {"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": ...}}. Any phase that fails makes the exit code non-zero.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# relative RMS error allowed between the serving path (bfloat16 weights,
# activations and cache) and the float32 reference run on the same
# bf16-rounded weights at "highest" matmul precision. bfloat16 keeps 8
# significant bits (unit roundoff 2^-8 ~ 0.4%); every block rounds the
# residual stream and half a dozen matmul results, ~100 roundings over 24
# layers that add like a random walk: ~10 x 0.23% ~ 2-3% of the logits'
# scale. 5e-2 holds that with room; a wrong page, position or mask moves
# the logits by their own scale (~1.0), and computing in an 8-bit float
# (unit roundoff 2^-4) would land near 40% — both fail.
LOGITS_RTOL = 5e-2

# |first-step loss on four chips - on one chip| allowed, gpt_350m. The two
# runs share weights, batch and dtype and differ in reduction order (tp
# splits every contraction, pp microbatches the mean) and in the decoder
# tail (fused kernels on one chip where the gate picks them, composed XLA
# on the mesh). bfloat16 moves a token's loss by ~1e-2 with either sign;
# the mean over 8192 tokens keeps what is systematic, well under 2e-2 on a
# loss of ~10.9. A lost psum or a doubled microbatch moves it by far more.
MESH_LOSS_ATOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What one run drives. Widths come from the configs; the rest are
    counts."""
    serve_cfg: object
    train_cfg: object
    mesh_cfg: object          # --chips 4: the model one chip cannot hold
    slots: int
    pages: int
    page_size: int
    prompt_lens: tuple
    new_tokens: int
    requests: int
    prefix_len: int
    tails: tuple              # (tail of A, tail of B)
    logits_prompt: int        # page-aligned, even number of pages
    train_batch: int
    train_seq: int
    train_steps: int
    request_timeout: float


def real_sizes() -> Sizes:
    from paddle_tpu.models.gpt import GPTConfig
    return Sizes(
        serve_cfg=GPTConfig.gpt3_1p3b(amp_dtype="bfloat16"),
        # the widths of benchmark/configs/gpt_350m_train.json
        train_cfg=GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                            max_position_embeddings=1024,
                            amp_dtype="bfloat16", attn_impl="flash"),
        mesh_cfg=GPTConfig.gpt3_1p3b(amp_dtype="bfloat16",
                                     attn_impl="flash"),
        slots=8, pages=1024, page_size=16,
        prompt_lens=(48, 200, 700, 1500), new_tokens=64, requests=12,
        prefix_len=256, tails=(40, 64), logits_prompt=128,
        train_batch=8, train_seq=1024, train_steps=4,
        request_timeout=900.0)


def rehearsal_sizes() -> Sizes:
    from paddle_tpu.models.gpt import GPTConfig
    tiny = GPTConfig.tiny(num_layers=2, amp_dtype="bfloat16")
    train = GPTConfig.tiny(num_layers=2, amp_dtype="bfloat16",
                           attn_impl="flash")
    return Sizes(
        serve_cfg=tiny, train_cfg=train, mesh_cfg=train,
        slots=4, pages=64, page_size=8,
        prompt_lens=(5, 27), new_tokens=6, requests=4,
        prefix_len=32, tails=(5, 8), logits_prompt=16,
        train_batch=4, train_seq=64, train_steps=4,
        request_timeout=300.0)


class Failed(Exception):
    """A check of this smoke did not hold."""


def check(cond, msg):
    if not cond:
        raise Failed(msg)


def say(*parts):
    print(*parts, flush=True)


def hbm(tag):
    """Per-device memory, where the backend reports it (the CPU does
    not)."""
    import jax
    for d in jax.devices():
        st = d.memory_stats()
        if st:
            say(f"  memory[{tag}] device {d.id}: "
                f"in_use={st['bytes_in_use'] / 2**30:.2f} GiB "
                f"peak={st['peak_bytes_in_use'] / 2**30:.2f} GiB")


@contextlib.contextmanager
def recorded_pallas_calls():
    """Every pallas_call built inside, as (kernel name, interpret). The
    kernel modules reach pallas_call through the `pl` module attribute,
    so one wrapper sees them all."""
    from jax.experimental import pallas as pl
    built = []
    orig = pl.pallas_call

    def recording(kernel, *args, **kw):
        fn = getattr(kernel, "func", kernel)
        built.append((getattr(fn, "__name__", repr(fn)),
                      bool(kw.get("interpret", False))))
        return orig(kernel, *args, **kw)

    pl.pallas_call = recording
    try:
        yield built
    finally:
        pl.pallas_call = orig


def bf16_params(cfg, seed):
    """Random weights on the device, cast to bfloat16 ON THE HOST first:
    the float32 copy (5.3 GB at gpt_1p3b) never reaches the device."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import init_gpt_params
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a.astype(jnp.bfloat16)),
        init_gpt_params(cfg, seed))


def tokens_of(rep) -> list[int]:
    return [int(t) for t in np.asarray(rep["tokens"]).ravel()]


def run_alone(eng, prompt, new_tokens, what) -> list[int]:
    """One request in process on an engine nobody else drives."""
    h = eng.submit(np.asarray(prompt, np.int32), new_tokens)
    eng.run_until_idle()
    check(h.status == "done", f"{what}: ended {h.status!r}: {h.error}")
    check(len(h.generated) == new_tokens,
          f"{what}: {len(h.generated)} tokens, asked {new_tokens}")
    return [int(t) for t in h.generated]


# ---------------------------------------------------------------------------
# logits
# ---------------------------------------------------------------------------

def logits_phase(S: Sizes, params) -> dict:
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import gpt_forward
    from paddle_tpu.serving import GPTDecodeModel

    cfg, ps = S.serve_cfg, S.page_size
    n0, extra = S.logits_prompt, 4
    ids = np.random.RandomState(11).randint(
        0, cfg.vocab_size, (n0 + extra,)).astype(np.int32)

    t0 = time.perf_counter()
    ref_cfg = dataclasses.replace(cfg, amp_dtype=None, attn_impl="xla",
                                  fused_blocks=False, remat=False)
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(
            lambda p, i: gpt_forward(p, i, ref_cfg))(p32, ids[None]))
    del p32
    ref = ref[0, n0 - 1:]                       # positions n0-1 .. n0+3
    ref_rms = float(np.sqrt(np.mean(np.square(ref))))
    say(f"  reference: float32 gpt_forward over {n0 + extra} tokens, "
        f"logits rms {ref_rms:.3f} ({time.perf_counter() - t0:.1f}s)")

    def rel(got, want):
        d = np.asarray(got, np.float32) - want
        check(np.all(np.isfinite(d)), "non-finite logits")
        return float(np.sqrt(np.mean(np.square(d)))) / ref_rms

    num_pages = 2 * (n0 + extra) // ps + 2
    n_req = -(-(n0 + extra) // ps)              # pages of this sequence
    M = 1 << (n_req - 1).bit_length()
    row = np.full((M,), num_pages, np.int32)    # fill = trash page
    row[:n_req] = np.arange(n_req)
    half = n0 // 2                              # page-aligned cached part
    # the dense prefill does not depend on the paged path: once for both
    model = GPTDecodeModel(cfg, params=params)
    prefill = jax.jit(model.prefill)
    prefilled = prefill(params, model.init_cache(num_pages, ps), ids[:n0],
                        np.int32(n0), row)
    half_cache, _ = prefill(params, model.init_cache(num_pages, ps),
                            ids[:half], np.int32(half), row)
    out = {}
    for impl in ("xla", "pallas"):
        t0 = time.perf_counter()
        model = GPTDecodeModel(cfg, params=params, attn_impl=impl)
        cache, lg = prefilled
        errs = [rel(lg, ref[0])]
        decode = jax.jit(model.decode)
        for t in range(n0, n0 + extra):
            cache, lg = decode(params, cache, ids[t:t + 1],
                               np.asarray([t], np.int32), row[None])
            errs.append(rel(lg[0], ref[t - n0 + 1]))
        # the shared-prefix path: the first half was prefilled dense, the
        # second half runs as a tail over the cached pages
        _, lg = jax.jit(model.prefill_tail)(
            params, half_cache, ids[half:n0], np.int32(half),
            np.int32(n0 - half), row)
        tail_err = rel(lg, ref[0])
        worst = max(*errs, tail_err)
        say(f"  {impl:>6}: prefill {errs[0]:.2e}  decode "
            f"{' '.join(f'{e:.2e}' for e in errs[1:])}  prefill_tail "
            f"{tail_err:.2e}  (rel rms vs float32, tolerance "
            f"{LOGITS_RTOL:.0e}; {time.perf_counter() - t0:.1f}s)")
        check(worst <= LOGITS_RTOL,
              f"{impl} paged path is {worst:.3e} from the float32 "
              f"reference, tolerance {LOGITS_RTOL:.0e}")
        out[impl] = worst
    return out


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def make_requests(S: Sizes) -> list[dict]:
    """More requests than slots; half greedy, half seeded top-p; one
    streamed."""
    rng = np.random.RandomState(7)
    reqs = []
    for i in range(S.requests):
        n = S.prompt_lens[i % len(S.prompt_lens)]
        r = {"prompt": rng.randint(0, S.serve_cfg.vocab_size,
                                   (n,)).astype(np.int32),
             "max_new_tokens": S.new_tokens, "stream": i == 2}
        if i % 2:
            r.update(temperature=0.8, top_p=0.9, seed=1000 + i)
        reqs.append(r)
    return reqs


def drive(client, reqs, timeout) -> tuple[list[dict], float]:
    """All requests at once over the wire; replies in request order."""
    def one(r):
        kw = {k: v for k, v in r.items() if k not in ("prompt", "stream")}
        if not r["stream"]:
            return client.generate(r["prompt"], timeout=timeout, **kw)
        streamed = []
        rep = client.generate(r["prompt"], timeout=timeout, stream=True,
                              on_token=lambda toks, _i: streamed.extend(toks),
                              **kw)
        rep["streamed"] = streamed
        return rep

    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=len(reqs)) as pool:
        replies = list(pool.map(one, reqs))
    return replies, time.perf_counter() - t0


def check_replies(replies, want_tokens, what):
    for i, rep in enumerate(replies):
        check(rep.get("status") == "done",
              f"{what}: request {i} ended {rep.get('status')!r}: "
              f"{rep.get('error')}")
        toks = tokens_of(rep)
        check(len(toks) == want_tokens,
              f"{what}: request {i} returned {len(toks)} tokens, asked "
              f"{want_tokens}")
        check(rep.get("streamed", toks) == toks,
              f"{what}: request {i} streamed frames differ from its "
              f"final reply")


def serve_phase(S: Sizes, params) -> dict:
    from paddle_tpu.serving import (Engine, GPTDecodeModel, ServingClient,
                                    ServingServer)
    from paddle_tpu.serving.engine import _bucket_len

    model = GPTDecodeModel(S.serve_cfg, params=params)   # gate picks paths
    eng = Engine(model, num_slots=S.slots, num_pages=S.pages,
                 page_size=S.page_size)
    reqs = make_requests(S)
    greedy = reqs[0]
    want = {f"prefill[{_bucket_len(n, S.page_size)}]"
            for n in S.prompt_lens}
    want.add(f"decode[slots={S.slots},pages={eng.max_pages_per_req}]")

    # the greedy request alone, in process
    t0 = time.perf_counter()
    alone = run_alone(eng, greedy["prompt"], S.new_tokens, "alone")
    t_alone = time.perf_counter() - t0

    with ServingServer(eng, "127.0.0.1:0") as srv:
        client = ServingClient(srv.endpoint,
                               timeout=S.request_timeout + 30.0)
        try:
            rep1, t1 = drive(client, reqs, S.request_timeout)
            check_replies(rep1, S.new_tokens, "pass 1")
            compiles1 = dict(eng.stats()["compiles"])
            rep2, t2 = drive(client, reqs, S.request_timeout)
            check_replies(rep2, S.new_tokens, "pass 2")
            wire, _ = drive(client, [greedy], S.request_timeout)
            check_replies(wire, S.new_tokens, "wire alone")
            st = eng.stats()
        finally:
            client.close()
    say(f"  buckets: {compiles1}")
    check(set(compiles1) == want,
          f"compiled buckets {sorted(compiles1)} != expected "
          f"{sorted(want)}")
    check(all(n == 1 for n in compiles1.values()),
          f"a bucket compiled more than once: {compiles1}")
    check(st["compiles"] == compiles1,
          f"the second pass compiled: {st['compiles']} vs {compiles1}")
    for i, (a, b) in enumerate(zip(rep1, rep2)):
        check(tokens_of(a) == tokens_of(b),
              f"request {i} gave different tokens on the second pass")
    mixed, wired = tokens_of(rep1[0]), tokens_of(wire[0])
    check(alone == mixed == wired,
          f"the same greedy request gave different tokens: alone "
          f"{alone[:8]}.., in the mix {mixed[:8]}.., over the wire "
          f"{wired[:8]}..")
    ntok = S.requests * S.new_tokens
    say(f"  alone (compiles 2 programs) {t_alone:.1f}s; pass 1 "
        f"{t1:.1f}s; pass 2 {t2:.2f}s = {ntok / t2:.0f} tokens/s over "
        f"the wire, closed loop, {S.requests} requests on {S.slots} slots")
    say(f"  completed={st['completed']} rejected={st['rejected']} "
        f"preemptions={st['preemptions']} "
        f"pool={st['pool']['num_pages']} pages")
    check(st["rejected"] == 0 and st["preemptions"] == 0,
          f"requests were rejected or preempted: {st}")
    return {"setup_s": t_alone + t1, "steady_s": t2}


def prefix_phase(S: Sizes, params) -> dict:
    from paddle_tpu.serving import Engine, GPTDecodeModel

    model = GPTDecodeModel(S.serve_cfg, params=params)
    eng = Engine(model, num_slots=S.slots, num_pages=S.pages,
                 page_size=S.page_size, prefix_cache_pages=S.pages // 4)
    rng = np.random.RandomState(13)
    V = S.serve_cfg.vocab_size
    prefix = rng.randint(0, V, (S.prefix_len,)).astype(np.int32)
    a = np.concatenate([prefix, rng.randint(0, V, (S.tails[0],))])
    b = np.concatenate([prefix, rng.randint(0, V, (S.tails[1],))])
    t0 = time.perf_counter()
    # A misses; B shares the prefix (prefill_tail); the bare prefix is a
    # whole-prompt hit (copy-on-write, no prefill); A again hits its own
    # pages and prefills a few tokens
    outs = [run_alone(eng, prompt, S.new_tokens, name) for name, prompt
            in (("A", a), ("B", b), ("prefix", prefix), ("A'", a))]
    st = eng.stats()
    say(f"  buckets: {st['compiles']}")
    say(f"  prefix cache: {st['prefix_cache']}")
    check(all(n == 1 for n in st["compiles"].values()),
          f"a bucket compiled more than once: {st['compiles']}")
    check(any(k.startswith("prefill_tail[") for k in st["compiles"]),
          "no prefill_tail bucket compiled")
    pc = st["prefix_cache"]
    check(pc["hits"] >= 3 and pc["cow_copies"] >= 1,
          f"expected 3 hits and a copy-on-write, got {pc}")
    same = next((i for i, (x, y) in enumerate(zip(outs[0], outs[3]))
                 if x != y), S.new_tokens)
    # reported, not required: the hit recomputes attention over pages and
    # in bfloat16 with random weights a rounding can move an argmax
    say(f"  A replayed through the cache agrees for {same}/"
        f"{S.new_tokens} tokens")
    return {"setup_s": time.perf_counter() - t0}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(S: Sizes, cfg, pallas_calls, pp=1, tp=1) -> dict:
    import jax
    from paddle_tpu.observability import perf
    from paddle_tpu.parallel.hybrid import HybridParallelTrainStep

    if pp * tp > 1:
        # said here, not left to the trainer's warning: the fused
        # decoder-tail kernels cannot be partitioned (parallel/hybrid.py)
        cfg = dataclasses.replace(cfg, fused_blocks=False)
    built0 = len(pallas_calls)
    t0 = time.perf_counter()
    step = HybridParallelTrainStep(
        cfg, pp=pp, tp=tp, n_microbatches=2 * pp if pp > 1 else None,
        grad_clip_norm=1.0)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (S.train_batch, S.train_seq)).astype(np.int32)
    t_init = time.perf_counter() - t0
    losses, secs = [], []
    for _ in range(S.train_steps):
        t0 = time.perf_counter()
        losses.append(float(jax.block_until_ready(step(ids))))
        secs.append(time.perf_counter() - t0)
    steady = float(np.mean(secs[1:]))
    built = sorted({name for name, _i in pallas_calls[built0:]})
    say(f"  hidden {cfg.hidden_size} x {cfg.num_layers} layers, "
        f"pp={pp} tp={tp}, attention {step.cfg.attn_impl}, fused tail "
        f"{step.cfg.fused_blocks}, batch {S.train_batch} x {S.train_seq}; "
        f"kernels built: {', '.join(built) or 'none'}")
    say(f"  loss {' '.join(f'{v:.4f}' for v in losses)}")
    say(f"  init {t_init:.1f}s, first step {secs[0]:.1f}s, then "
        f"{steady * 1e3:.0f} ms/step = "
        f"{S.train_batch * S.train_seq / steady:.0f} tokens/s")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on one repeated batch: {losses}")
    # what was asked for ran: the trainer kept the attention it was given
    # and built its forward and backward kernels, unless the gate timed
    # them at this step's (per-shard) shape and chose the XLA side
    check((step.cfg.attn_impl, step.cfg.fused_blocks)
          == (cfg.attn_impl, cfg.fused_blocks),
          f"asked for attention {cfg.attn_impl!r}, fused tail "
          f"{cfg.fused_blocks}; the trainer ran {step.cfg.attn_impl!r}, "
          f"{step.cfg.fused_blocks}")
    if cfg.attn_impl == "flash" \
            and not {"kfn", "dq_kfn", "dkv_kfn"} <= set(built):
        lost = [k for k, v in perf.kernels().items()
                if k.startswith("('flash_attention'")
                and v["winner"] != "pallas"]
        check(lost, f"attn_impl='flash' built only {built} and no flash "
                    f"gate chose the XLA side")
    if pp * tp > 1:
        hbm(f"pp={pp} tp={tp}")
    return {"setup_s": t_init + secs[0], "steady_s": steady,
            "first_loss": losses[0]}


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def kernels_phase(pallas_calls, decided_before, rehearse: bool) -> dict:
    from paddle_tpu.observability import perf
    rows = {k: r for k, r in perf.kernels().items()
            if k not in decided_before}
    say(f"  {len(rows)} gate decisions (compiled execution on concrete "
        f"device arrays; per call, median of 3 batches of back-to-back "
        f"calls; the default keeps a tie):")
    for key, r in rows.items():
        ms = " ".join(f"{c}={v:.4f}ms" for c, v in r["candidates_ms"].items())
        say(f"    {key} -> {r['winner']} ({r['source']})  {ms}"
            + "".join(f"  {c}: ERROR {e}" for c, e in r["errors"].items()))
    kinds = {}
    for name, interp in pallas_calls:
        kinds[(name, interp)] = kinds.get((name, interp), 0) + 1
    say(f"  {len(pallas_calls)} pallas_call builds:")
    for (name, interp), n in sorted(kinds.items()):
        say(f"    {name} x{n} interpret={interp}")
    bad = [f"{key}/{c}: {e}" for key, r in rows.items()
           for c, e in r["errors"].items()]
    check(not bad, "gate candidates failed to run: " + "; ".join(bad))
    if not rehearse:
        interp = sorted({n for n, i in pallas_calls if i})
        check(not interp, f"kernels built in interpret mode on the "
                          f"chip: {interp}")
    return {}


# ---------------------------------------------------------------------------

def one_chip_phases(S: Sizes, phase, pallas_calls):
    t0 = time.perf_counter()
    params = bf16_params(S.serve_cfg, 0)
    say(f"weights: {S.serve_cfg.hidden_size} x {S.serve_cfg.num_layers} "
        f"layers, bfloat16, seed 0 ({time.perf_counter() - t0:.1f}s)")
    phase("logits", logits_phase, S, params)
    phase("serve", serve_phase, S, params)
    phase("prefix", prefix_phase, S, params)
    del params
    gc.collect()
    hbm("after serving")
    phase("train", train_phase, S, S.train_cfg, pallas_calls)


def mesh_phases(S: Sizes, phase, pallas_calls, results):
    import jax
    phase("train", train_phase, S, S.train_cfg, pallas_calls)
    phase("train pp2xtp2", train_phase, S, S.train_cfg, pallas_calls,
          pp=2, tp=2)
    one, four = results["train"], results["train pp2xtp2"]
    if one["ok"] and four["ok"]:
        d = abs(one["first_loss"] - four["first_loss"])
        say(f"  first-step loss: one chip {one['first_loss']:.4f}, "
            f"pp=2 x tp=2 {four['first_loss']:.4f}, |diff| {d:.4f} "
            f"(tolerance {MESH_LOSS_ATOL})")
        if d > MESH_LOSS_ATOL:
            four.update(ok=False, error=f"first-step loss differs from "
                                        f"the one-chip run by {d:.4f}")
    phase("train 1p3b pp2xtp2", train_phase, S, S.mesh_cfg, pallas_calls,
          pp=2, tp=2)
    mesh = np.array(jax.devices()[:4]).reshape(2, 2)
    say(f"  mesh (pp, tp) in jax.devices() id order: "
        f"{[[(d.id, getattr(d, 'coords', None)) for d in r] for r in mesh]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny widths on the CPU; never a pass")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the trainer over a pp=2 x tp=2 mesh")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    if args.rehearse:
        # before jax starts a backend (a no-op where one already runs,
        # as in the test suite)
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") \
                + " --xla_force_host_platform_device_count=8"

    # off jax until the compile cache is placed. A rehearsal leaves it
    # alone: XLA:CPU reloads cached code with machine-feature warnings,
    # and the process (a test run) lives on after this function.
    from paddle_tpu.utils.compile_cache import (cache_dir, cache_entries,
                                                setup_compile_cache)
    if not args.rehearse:
        setup_compile_cache()
    entries_before = cache_entries()

    import jax
    import jaxlib
    from importlib.metadata import version
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU and will not carry on elsewhere: "
              f"jax found platform={device['platform']!r} "
              f"kind={device['kind']!r} count={device['count']} "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
              f"--rehearse runs the code on the CPU", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, jax found {len(devs)}", file=sys.stderr)
        return 2

    cache_events = {"hits": 0, "misses": 0}

    def on_event(name, **_kw):
        # jax counts a miss when it WRITES an entry (programs that took
        # over its 1 s threshold to compile), a hit when it reads one
        if name.startswith("/jax/compilation_cache/cache_"):
            cache_events[name.rsplit("_", 1)[1]] += 1

    if not args.rehearse:
        jax.monitoring.register_event_listener(on_event)

    mode = "rehearsal (never a pass)" if args.rehearse else "chip"
    say(f"chip_smoke [{mode}] platform: {device['platform']}  "
        f"device_kind: {device['kind']}  count: {device['count']}")
    say(f"versions: python {sys.version.split()[0]} jax {jax.__version__} "
        f"jaxlib {jaxlib.__version__} libtpu {version('libtpu')}")
    say(f"compile cache: {cache_dir()} ({entries_before} entries before"
        f"{'; not used by a rehearsal' if args.rehearse else ''})")

    S = rehearsal_sizes() if args.rehearse else real_sizes()
    results = {}

    def phase(name, fn, *a, **kw):
        say(f"[{name}]")
        t0 = time.perf_counter()
        try:
            results[name] = dict(fn(*a, **kw), ok=True)
        except Exception as e:
            traceback.print_exc()
            results[name] = {"ok": False,
                             "error": f"{type(e).__name__}: {e}"}
            say(f"  FAILED: {type(e).__name__}: {e}")
        results[name]["wall_s"] = time.perf_counter() - t0
        gc.collect()

    # this run's gate decisions (a fresh process has none before it)
    from paddle_tpu.observability import perf
    decided_before = set(perf.kernels())
    with recorded_pallas_calls() as pallas_calls:
        if args.chips == 1:
            one_chip_phases(S, phase, pallas_calls)
        else:
            mesh_phases(S, phase, pallas_calls, results)
        phase("kernels", kernels_phase, pallas_calls, decided_before,
              args.rehearse)

    entries_after = cache_entries()
    say(f"compile cache: {entries_after} entries after "
        f"(+{entries_after - entries_before}); {cache_events['hits']} "
        f"hits, {cache_events['misses']} misses written")
    say("phase                 ok     set-up s   steady s     wall s")
    for name, r in results.items():
        cols = "   ".join("       -" if r.get(k) is None else f"{r[k]:8.2f}"
                          for k in ("setup_s", "steady_s", "wall_s"))
        say(f"{name:<20} {str(r['ok']):>5}   {cols}")
    say(f"total {time.perf_counter() - t_start:.1f}s")
    passed = all(r["ok"] for r in results.values())
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True,
                          "rehearsal_passed": passed, "device": device}))
    elif passed:
        print(json.dumps({"ok": True, "device": device}))
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Step 0 of ISSUE 31: does feeding a decode's tokens to the next decode
on the device, and reading them one call behind, hide the host's part of
a serving step? The cell's engine (gpt_1p3b_serve, 32 slots taken by the
cell's own prompts) is warmed through `Engine.step`, then its jitted
decode is driven in a bare loop, with the batch built on the host
between calls as `Engine.step` builds it (eight numpy arrays, eight
transfers):

  (a) the tokens are read after every call, and fed back from the host;
  (b) call k's tokens stay on the device as call k+1's input, and the host
      reads call k-1's after it has dispatched call k.

Then (a) again, since the contexts grow by a token a call: (b) lies
between the two. On the chip, from the tree's root:
    chiprun --timeout 900 -- python3 scripts/pr31_step0.py
Prints one line `STEP0 {...}`: milliseconds a call.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import harness, traffic as traffic_lib  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="gpt_1p3b_serve.decode_closed64")
    ap.add_argument("--seed", type=int, default=2147495031)
    ap.add_argument("--calls", type=int, default=150)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("PADDLE_TPU_AUTOBENCH_CACHE", "0")
        for part in ("config", "traffic"):
            harness._merge(cell[part], cell[part].get("rehearsal", {}))
    else:
        harness.place_caches()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.runners import serve
    from paddle_tpu.serving.engine import _FROM_DEVICE
    from paddle_tpu.serving.sampling import seed_to_key

    run_args = argparse.Namespace(seed=args.seed, seconds=1, trace=0)
    ctx = harness.Context(cell, run_args, time.perf_counter(), args.rehearse)
    eng, _params = serve._engine(ctx)
    S, calls = eng.num_slots, args.calls
    stream = traffic_lib.RequestStream(ctx.traffic, ctx.config["sizes"][
        "vocab_size"], ctx.seed)
    reqs = []
    for _ in range(S):
        item = stream.next()
        # room for both loops' writes behind the prompt
        room = eng.max_seq_len - item["prompt_len"]
        reqs.append(eng.submit(
            item["prompt"], min(room, 3 * calls + 8), seed=item["seed"],
            temperature=item["temperature"], top_k=item["top_k"],
            top_p=item["top_p"]))
    for _ in range(6):
        eng.step()
    assert all(r.status == "running" for r in reqs), \
        [r.status for r in reqs]
    # the engine at rest: read what is in flight, then drive its program
    with eng._lock:
        fl, eng._inflight = eng._inflight, None
        last = np.zeros((S,), np.int32)
        base = np.zeros((S,), np.int32)
        for r in reqs:
            n = len(r.generated) + 1
            last[r.slot] = int(np.asarray(fl.tokens)[r.slot])
            base[r.slot] = int(r.prompt.size) + n - 1
        room = min(eng.max_seq_len - 1 - int(base.max()), 3 * calls + 4)
        calls = min(calls, room // 3)

        def build(k, tokens):
            """The batch of call k, as Engine._step_phases builds it."""
            positions = np.zeros((S,), np.int32)
            tables = np.full((S, eng.max_pages_per_req), eng.trash_page,
                             np.int32)
            temps = np.zeros((S,), np.float32)
            topks = np.zeros((S,), np.int32)
            topps = np.ones((S,), np.float32)
            seeds = np.zeros((S, 2), np.uint32)
            steps = np.zeros((S,), np.int32)
            toks = np.zeros((S,), np.int32)
            for r in reqs:
                i = r.slot
                toks[i] = tokens[i]
                positions[i] = base[i] + k
                tables[i] = eng._row(r)
                temps[i], topks[i], topps[i] = \
                    r.temperature, r.top_k, r.top_p
                seeds[i] = seed_to_key(r.seed if r.seed is not None
                                       else r.id)
                steps[i] = positions[i] - int(r.prompt.size) + 1
            return (jnp.asarray(toks), jnp.asarray(positions),
                    jnp.asarray(tables), jnp.asarray(temps),
                    jnp.asarray(topks), jnp.asarray(topps),
                    jnp.asarray(seeds), jnp.asarray(steps))

        def call(k, tokens, prev):
            toks, *rest = build(k, tokens)
            eng.cache, out = eng._decode(eng.model.params, eng.cache, toks,
                                         prev, *rest)
            return out

        def read_every_call(k0):
            nonlocal last
            t = []
            for k in range(k0, k0 + calls):
                t0 = time.perf_counter()
                out = call(k, last, eng._no_tokens)
                last = np.asarray(out)
                t.append(time.perf_counter() - t0)
            return t

        # (a) read after every call
        t_a = read_every_call(0)
        # (b) one call behind: call k runs while the host builds k+1
        t_b = []
        from_device = np.full((S,), _FROM_DEVICE, np.int32)
        prev = call(calls, last, eng._no_tokens)
        for k in range(calls + 1, 2 * calls):
            t0 = time.perf_counter()
            out = call(k, from_device, prev)
            last = np.asarray(prev)         # the call before's tokens
            prev = out
            t_b.append(time.perf_counter() - t0)
        last = np.asarray(prev)
        # (a) again, at the contexts (b) has grown: (b) lies between them
        t_a2 = read_every_call(2 * calls)
    ms = lambda v: {"p50": 1e3 * statistics.median(v),  # noqa: E731
                    "mean": 1e3 * sum(v) / len(v),
                    "p90": 1e3 * sorted(v)[int(0.9 * len(v))], "n": len(v)}
    ctx_tokens = int(base.sum()) + S * calls * 3 // 2
    print("STEP0 " + json.dumps({
        "device": jax.devices()[0].device_kind, "slots": S,
        "mean_context": ctx_tokens / S,
        "a_read_every_call_ms": ms(t_a[5:]),
        "b_read_one_behind_ms": ms(t_b[5:]),
        "a_again_ms": ms(t_a2[5:])}))


if __name__ == "__main__":
    main()

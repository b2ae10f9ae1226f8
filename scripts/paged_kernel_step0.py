"""The paged-attention Pallas kernel alone, at the serving cells' shapes
(ISSUE 29, Step 0). Run from the root of a tree, on the chip:

    python3 scripts/paged_kernel_step0.py [--out chiprun_out/step0.json]

It imports `paddle_tpu` from the tree it is started in, so the same file
times the parent's kernel (copy it into the parent's checkout) and the
change's. For each cell it times one decode step's worth of kernel calls
(one call a layer of attention, the layer a traced index into a stacked
pool, as the decode bodies call it) in three cases:

  (a) every slot at ctx = 1, its table all trash: what a slot costs
      whatever it holds (the parent: its grid, one step a table entry);
  (b) every slot full: (b) - (a) is the arithmetic and the copies;
  (c) the cell's own live share, every slot at that share of its table;
  (d) the same share as a mean, the slots ragged: uniform between one
      token and twice the share (what a cell's batch looks like).

and prints, beside the milliseconds, the live K/V bytes of the case over
the chip's published bandwidth (`benchmark/lib/peaks.py`: the least time
its memory allows, counted as the benchmark's roofline readers do) and the widest
difference of one call from the XLA path's on the same arguments.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.getcwd())
from benchmark.lib import peaks   # noqa: E402
from paddle_tpu.ops.paged_attention import (   # noqa: E402
    paged_attention_pallas, paged_attention_xla)

# S slots, H heads over Hkv of d, a table of M pages of ps tokens, P pages
# in the pool (+ trash), L calls a decode step, `live`: the share of a
# slot's table that holds tokens in the cell (ISSUE 29, from the ledger's
# PR 28 lines). The pools hold `stack` layers: the kernel's time does not
# depend on the stack's depth, and 24 layers of GPT's would be 9.7 GB.
CELLS = {
    "gpt_1p3b_serve.decode_closed64": dict(
        S=32, H=16, Hkv=16, d=128, ps=16, M=128, P=3072, L=24, stack=4,
        fused=False, live=0.22),
    "gpt_1p3b_serve.mixed_open": dict(
        S=32, H=16, Hkv=16, d=128, ps=16, M=128, P=3072, L=24, stack=4,
        fused=False, live=0.10),
    "lfm2_8b_a1b_serve.decode_closed128": dict(
        S=64, H=32, Hkv=8, d=64, ps=16, M=256, P=16384, L=3, stack=3,
        fused=True, live=0.20),
}


def _case(c, ctx, rng):
    """(table, ctx_lens) for ctx [S] tokens a slot: its live pages drawn
    from the pool (without repeats while the pool has enough: GPT's holds
    3072 pages and 32 full tables name 4096), the rest of its row trash."""
    S, M, P, ps = c["S"], c["M"], c["P"], c["ps"]
    n = -(-ctx // ps)
    pages = rng.permutation(max(P, int(n.sum()))) % P
    table = np.full((S, M), P, np.int32)
    for s, at in enumerate(np.cumsum(n) - n):
        table[s, :n[s]] = pages[at:at + n[s]]
    return jnp.asarray(table), jnp.asarray(ctx, jnp.int32)


def _step(c):
    """One decode step's kernel calls as one jitted program. Each call's
    output feeds the next call's query, so none can be dropped."""
    L, stack = c["L"], c["stack"]

    @jax.jit
    def step(q, k, v, table, ctx):
        def layer(q, l):
            o = paged_attention_pallas(q, k, v, table, ctx, layer=l % stack,
                                       interpret=False)
            return (q + o * 1e-3).astype(q.dtype), None
        q, _ = jax.lax.scan(layer, q, jnp.arange(L, dtype=jnp.int32))
        return q
    return step


def _time_ms(fn, args, reps=20):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(ts))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="")
    ap.add_argument("--cell", action="append", choices=sorted(CELLS))
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("needs a TPU: a kernel's time is a chip reading")
    hbm_bytes_s = peaks.peak(dev.device_kind)["hbm_bytes_s"]
    out = {"device": dev.device_kind, "cells": {}}
    for name in a.cell or sorted(CELLS):
        c = CELLS[name]
        rng = np.random.RandomState(0)
        w = 2 * c["d"] if c["fused"] else c["d"]
        shape = (c["stack"], c["P"] + 1, c["ps"], c["Hkv"], w)
        key = jax.random.PRNGKey(0)
        k = jax.random.normal(key, shape, jnp.bfloat16)
        v = None if c["fused"] else jax.random.normal(
            jax.random.fold_in(key, 1), shape, jnp.bfloat16)
        q = jax.random.normal(jax.random.fold_in(key, 2),
                              (c["S"], c["H"], c["d"]), jnp.bfloat16)
        step = _step(c)
        full = c["M"] * c["ps"]
        rows = {}
        live = max(1, int(round(c["live"] * full)))
        ones = np.ones((c["S"],), np.int64)
        for case, ctx in (("a_ctx1", ones), ("b_full", full * ones),
                          ("c_live", live * ones),
                          ("d_ragged", rng.randint(1, 2 * live, c["S"]))):
            table, lens = _case(c, ctx, rng)
            ms = _time_ms(step, (q, k, v, table, lens))
            need = (c["L"] * peaks.paged_attention_bytes(
                int(ctx.sum()), c["Hkv"], c["d"]) / hbm_bytes_s * 1e3)
            got = paged_attention_pallas(q, k, v, table, lens, layer=1,
                                         interpret=False)
            want = paged_attention_xla(q, k, v, table, lens, layer=1)
            rows[case] = {"ctx_mean": float(ctx.mean()),
                          "ms_a_step": round(ms, 4),
                          "live_bytes_ms": round(need, 4),
                          "of_roofline_pct": round(100 * need / ms, 2),
                          "widest_diff_from_xla": float(jnp.max(jnp.abs(
                              got.astype(jnp.float32)
                              - want.astype(jnp.float32))))}
            print(name, case, rows[case], flush=True)
        out["cells"][name] = rows
        del k, v
    print(json.dumps(out))
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()

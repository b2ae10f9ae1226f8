#!/usr/bin/env python3
"""Step 0 of the windowed cell's two paged-attention calls, alone on the
chip (PR 40): the window call (`first`, `ring`: 5 sliding layers' pool, a
ring of 33 pages a slot) and the full layer's call (1 layer, a table of 288
pages) at Trinity-Mini's shapes, 64 slots, contexts drawn from the cell's
mix: milliseconds a call (median of `--reps`, after one warm call), the
model's bytes at the HBM's peak beside them; for the full layer's call the
Pallas kernel beside the XLA gather and the widest difference between the
two. The window call has one implementation, the gather: PR 40's first
tree also had a kernel that walked a ring's live pages, which this script
read at 4.46 ms beside the gather's 2.10, and which went (PERF.md, section
6). From a tree's root, on the chip; `--rehearse` here (tiny, interpret mode, no
number is a device metric).

    chiprun -- python3 scripts/window_kernel_step0.py --out chiprun_out/step0.json
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.ops import paged_attention as pa  # noqa: E402


def contexts(S, seed, lo, hi):
    """Resident contexts of S slots: lognormal prompts (median 4,096,
    sigma 1) clipped as the mix clips them, plus a uniform part of the
    output."""
    rng = np.random.RandomState(seed)
    p = np.clip(np.exp(np.log(4096) + rng.randn(S)), lo, hi)
    return np.minimum(p + rng.randint(1, 1536, S), hi).astype(np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    if a.rehearse:
        S, H, Hkv, d, ps, W, Pg, Pw, M, dt = 4, 8, 2, 128, 4, 8, 60, 20, 12, jnp.float32
        ctx = np.asarray([3, 9, 30, 47], np.int32)
    else:
        S, H, Hkv, d, ps, W, Pg, Pw, M, dt = 64, 32, 4, 128, 64, 2048, 7680, 2112, 288, jnp.bfloat16
        ctx = contexts(S, 0, 256, 18000)
    R = W // ps + 1
    interpret = a.rehearse
    rng = np.random.RandomState(1)
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (S, H, d), dt)
    pools = {"window": (5, Pw + 1, R), "full": (1, Pg + 1, M)}
    first = np.maximum(ctx - W, 0).astype(np.int32)
    peak = 819e9
    rows = {"ctx_mean": float(ctx.mean()), "window_rows": int(np.minimum(ctx, W).sum())}
    for name, (L, P, width) in pools.items():
        k = jax.random.normal(jax.random.fold_in(key, 1), (L, P, ps, Hkv, d), dt)
        v = jax.random.normal(jax.random.fold_in(key, 2), (L, P, ps, Hkv, d), dt)
        table = jnp.asarray(rng.randint(0, P - 1, (S, width)), jnp.int32)
        kw = {"first": jnp.asarray(first), "ring": R} if name == "window" else {}
        need = (np.minimum(ctx, W).sum() if name == "window" else ctx.sum()) \
            * 2 * Hkv * d * jnp.dtype(dt).itemsize
        outs = {}
        for impl in ("xla",) if name == "window" else ("xla", "pallas"):
            fn = pa.paged_attention_xla if impl == "xla" else pa.paged_attention_pallas
            extra = {"interpret": interpret} if impl == "pallas" else {}
            call = jax.jit(lambda q, k, v, t, c, l, fn=fn, extra=extra: fn(
                q, k, v, t, c, layer=l, **kw, **extra))
            args = (q, k, v, table, jnp.asarray(ctx), jnp.zeros((), jnp.int32))
            try:
                o = jax.block_until_ready(call(*args))
            except Exception as e:      # Mosaic's refusal, or no room
                rows[f"{name}.{impl}"] = f"FAILED {str(e)[:300]}"
                continue
            ts = []
            for _ in range(1 if a.rehearse else a.reps):
                t0 = time.perf_counter()
                jax.block_until_ready(call(*args))
                ts.append(time.perf_counter() - t0)
            outs[impl] = np.asarray(o, np.float32)
            rows[f"{name}.{impl}"] = {
                "ms": 1e3 * float(np.median(ts)),
                "need_ms_at_peak": 1e3 * need / peak,
                "roofline_pct": 100 * need / peak / float(np.median(ts))}
        if len(outs) == 2:
            rows[f"{name}.widest_difference"] = float(
                np.max(np.abs(outs["xla"] - outs["pallas"])))
        del k, v
    rows["rehearsal"] = bool(a.rehearse)
    rows["device"] = jax.devices()[0].device_kind
    print(json.dumps(rows))
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(rows, f)


if __name__ == "__main__":
    main()

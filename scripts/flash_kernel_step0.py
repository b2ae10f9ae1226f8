"""The flash-attention kernel's three calls alone, at the training cells'
shapes (ISSUE 48, Step 0). Run from the root of a tree, on the chip:

    python3 scripts/flash_kernel_step0.py [--cells a,b] [--sweep 0,256]
        [--out chiprun_out/flash_step0.jsonl]

It imports `paddle_tpu` from the tree it is started in and goes through
`flash_attention` alone, so the same file times the parent's kernel (copy
it into the parent's checkout) and the change's. For each shape it times
four programs, each one `flash_attention` under `jax.grad`:

  fwd          the forward call;
  fwd+dq       the gradient by q alone (XLA drops the dk/dv call);
  fwd+dkv      the gradient by k and v (XLA drops the dq call);
  all          the gradient by all three, a layer's four calls but one
               (under remat the forward runs once more);

and prints the calls' milliseconds (dq = fwd+dq - fwd, dkv = fwd+dkv -
fwd), beside the share of the chip's bf16 peak that the mask's useful
pairs come to in that time, counted as the benchmark's rooflines count
them (`benchmark/lib/peaks.py::causal_attention_call_flops`: 2, 3 and 4
products of the lower triangle, or of the band). `--sweep` is a list of
tile sizes, each a pass over the cells that hands every call that
`block_q` and `block_k` (0: what the call chooses).
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
from paddle_tpu.ops import pallas_attention as pa   # noqa: E402

# B sequences of T positions, H query heads over Hkv of d (values dv wide),
# a band of `window`; `fwd_only`: a serving prefill, no backward
CELLS = {
    "gpt_350m_train.b16s1024": dict(B=16, H=16, Hkv=16, T=1024, d=64),
    "mellum2_12b_a2p5b_train.b2s8192/band": dict(
        B=2, H=32, Hkv=4, T=8192, d=128, window=1024),
    "mellum2_12b_a2p5b_train.b2s8192/full": dict(
        B=2, H=32, Hkv=4, T=8192, d=128),
    "gpt_1p3b_train_pp2tp2.mb2x8s1024": dict(B=2, H=8, Hkv=8, T=1024, d=128),
    "kanana2_30b_a3b_serve/prefill8192": dict(
        B=1, H=32, Hkv=32, T=8192, d=192, dv=128, block_q=256,
        fwd_only=True),
    "kanana2_30b_a3b_serve/prefill4096": dict(
        B=1, H=32, Hkv=32, T=4096, d=192, dv=128, block_q=256,
        fwd_only=True),
    "kanana2_30b_a3b_serve/prefill1024": dict(
        B=1, H=32, Hkv=32, T=1024, d=192, dv=128, block_q=256,
        fwd_only=True),
    "encoder.b16s512": dict(B=16, H=12, Hkv=12, T=512, d=64, causal=False),
    # the windowed serving prefill's two calls (PR 50; beside XLA's row
    # blocks: scripts/prefill_flash_step0.py)
    "trinity_mini_serve/prefill16384/band": dict(
        B=1, H=32, Hkv=4, T=16384, d=128, window=2048, fwd_only=True),
    "trinity_mini_serve/prefill16384/full": dict(
        B=1, H=32, Hkv=4, T=16384, d=128, fwd_only=True),
    "trinity_mini_serve/prefill4096/band": dict(
        B=1, H=32, Hkv=4, T=4096, d=128, window=2048, fwd_only=True),
    "trinity_mini_serve/prefill4096/full": dict(
        B=1, H=32, Hkv=4, T=4096, d=128, fwd_only=True),
    "trinity_mini_serve/prefill1024/full": dict(
        B=1, H=32, Hkv=4, T=1024, d=128, fwd_only=True),
}


def _pairs(T, causal, window):
    """Score pairs the mask lets through, one head."""
    if not causal:
        return T * T
    w = min(window or T, T)
    return w * (w + 1) // 2 + (T - w) * w


def _time(fn, args, reps):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def measure(name, c, blocks, reps):
    B, H, Hkv, T, d = c["B"], c["H"], c["Hkv"], c["T"], c["d"]
    dv, causal, window = c.get("dv", d), c.get("causal", True), c.get("window")
    rng = np.random.RandomState(0)
    mk = lambda h, w: jnp.asarray(rng.randn(B, h, T, w), jnp.bfloat16)
    q, k, v = mk(H, d), mk(Hkv, d), mk(Hkv, dv)
    kw = dict(causal=causal, window=window, scale=d ** -0.5)
    if blocks:
        kw.update(block_q=blocks, block_k=blocks)
    elif "block_q" in c:
        kw["block_q"] = c["block_q"]
    w = jnp.asarray(rng.randn(B, H, T, dv), jnp.bfloat16)
    loss = lambda q, k, v: jnp.sum(
        (pa.flash_attention(q, k, v, **kw) * w).astype(jnp.float32))
    progs = {"fwd": jax.jit(lambda q, k, v: pa.flash_attention(q, k, v,
                                                               **kw))}
    if not c.get("fwd_only"):
        progs.update({"fwd+dq": jax.jit(jax.grad(loss, 0)),
                      "fwd+dkv": jax.jit(jax.grad(loss, (1, 2))),
                      "all": jax.jit(jax.grad(loss, (0, 1, 2)))})
    ms = {n: _time(f, (q, k, v), reps) for n, f in progs.items()}
    if "all" in ms:
        ms["dq"] = ms["fwd+dq"] - ms["fwd"]
        ms["dkv"] = ms["fwd+dkv"] - ms["fwd"]
    out = {"cell": name, "blocks": blocks or "auto", "ms": ms}
    dev = jax.devices()[0]
    if dev.platform == "tpu":       # a share of the chip's peak: the chip's
        peak = peaks.peak(dev.device_kind)["flops_bf16"]
        unit = 2.0 * B * H * _pairs(T, causal, window) * (d + dv) / 2
        out["share_of_bf16_peak_pct"] = {
            n: 100 * u * unit / (ms[n] * 1e-3) / peak
            for n, u in (("fwd", 2), ("dq", 3), ("dkv", 4)) if n in ms}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--sweep", default="0")
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--tag", default="")
    ap.add_argument("--shrink", type=int, default=1,
                    help="divide T, and a band, by this (a rehearsal "
                         "off the chip: its times mean nothing)")
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    dev = jax.devices()[0]
    rows = []
    for blocks in a.sweep.split(","):
        for name in a.cells.split(","):
            c = dict(CELLS[name])
            c["T"] //= a.shrink
            if c.get("window"):
                c["window"] //= a.shrink
            row = measure(name, c, int(blocks), a.reps)
            row.update(tag=a.tag, platform=dev.platform,
                       device_kind=dev.device_kind)
            rows.append(row)
            print(json.dumps(row), flush=True)
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Step 0 of the windowed prefill's attention (PR 50): the two calls of a
Trinity-Mini prefill alone (B 1, 32 query heads over 4 KV heads of 128,
bf16; a sliding layer's band of 2,048 and the full layer's triangle), at
the buckets the engine pads to, each in its two spellings:

  pallas   `ops/pallas_attention.py::flash_attention` as
           `models/layers.py::gated_causal_attention` calls it (the
           transposes to heads-first and back are part of the call)
  xla      `models/afmoe.py::banded_causal_attention`: float32 scores in
           row blocks, what the prefill ran before PR 50

(the two candidates of the gate's own trial, `layers._gate_flash`).

Milliseconds a call (median of `--reps` samples, a sample a batch of
back-to-back calls under one sync), the share of the chip's bf16 peak that
the mask's pairs come to in the kernel's time (counted as
`benchmark/lib/peaks.py` counts a forward call: 2 products of 128 over the
pairs the mask lets through, 32 heads), and the widest difference between
the two outputs beside the widest output. From a tree's root, on the chip:

    chiprun -- python3 scripts/prefill_flash_step0.py --out chiprun_out/pr50/step0.jsonl

`--compile` here gives Mosaic's verdict and the compiler's memory account
of each spelling at the real shapes for a described v5e, with no chip;
`--rehearse` runs T / 64 and a band / 64 in interpret mode (no number of
either is a device metric).
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.lib import peaks  # noqa: E402
from paddle_tpu.models import afmoe, layers  # noqa: E402
from paddle_tpu.ops import pallas_attention  # noqa: E402
from scripts.flash_kernel_step0 import _pairs  # noqa: E402

H, HKV, D, WINDOW = 32, 4, 128, 2048
BUCKETS = (256, 1024, 4096, 16384)


def spellings(T, window):
    """name -> fn(q, k, v) -> [1, T, H d]: the gate's two candidates."""
    _, cands, _ = layers._gate_flash(
        1, H, HKV, T, D, jnp.bfloat16, window, D ** -0.5,
        afmoe.banded_causal_attention)
    return {n: (lambda q, k, v, f=f: f(q, k, v).reshape(1, T, H * D))
            for n, f in cands.items()}


def shapes(T, sharding=None):
    kw = {} if sharding is None else {"sharding": sharding}
    return tuple(jax.ShapeDtypeStruct((1, T, h, D), jnp.bfloat16, **kw)
                 for h in (H, HKV, HKV))


def time_ms(fn, args, reps):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    one = time.perf_counter() - t0
    calls = max(1, min(50, int(0.2 / max(one, 1e-9))))
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) / calls * 1e3)
    return float(np.median(samples)), float(max(samples) - min(samples))


def measure(T, window, reps):
    keys = jax.random.split(jax.random.PRNGKey(T), 3)
    args = tuple(jax.random.normal(k, s.shape, jnp.float32).astype(s.dtype)
                 for k, s in zip(keys, shapes(T)))
    row = {"T": T, "window": window}
    outs = {}
    for name, fn in spellings(T, window).items():
        jfn = jax.jit(fn)
        try:
            row[f"{name}_ms"], row[f"{name}_spread_ms"] = time_ms(
                jfn, args, reps)
            outs[name] = np.asarray(jfn(*args).astype(jnp.float32))
        except Exception as e:      # a spelling the device refuses
            row[f"{name}_error"] = f"{type(e).__name__}: {e}"[:300]
    if len(outs) == 2:
        row["widest_diff"] = float(np.max(np.abs(outs["pallas"]
                                                 - outs["xla"])))
        row["widest_output"] = float(np.max(np.abs(outs["xla"])))
    dev = jax.devices()[0]
    if dev.platform == "tpu" and "pallas_ms" in row:
        flops = 2 * 2.0 * H * _pairs(T, True, window) * D
        row["pallas_share_of_bf16_peak_pct"] = 100 * flops / (
            row["pallas_ms"] * 1e-3) / peaks.peak(dev.device_kind)[
                "flops_bf16"]
    return row


def compile_for_v5e(T, window):
    """Mosaic's and XLA's verdict for a described chip: nothing runs."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    # the kernel for the chip, not the interpreter: this mode only
    pallas_attention._interpret = lambda: False
    row = {"T": T, "window": window}
    for name, fn in spellings(T, window).items():
        try:
            c = jax.jit(fn).lower(*shapes(T, one_chip)).compile()
            m = c.memory_analysis()
            row[name] = {"temp_mib": m.temp_size_in_bytes / 2 ** 20,
                         "mosaic_calls": c.as_text().count(
                             "tpu_custom_call")}
        except Exception as e:
            row[name] = f"{type(e).__name__}: {e}"[:400]
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--buckets", default=",".join(map(str, BUCKETS)))
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    shrink = 64 if a.rehearse else 1
    rows = []
    for T in map(int, a.buckets.split(",")):
        T //= shrink
        for window in (WINDOW // shrink, None):
            if window is not None and window >= T:
                continue    # as gated_causal_attention: no band there
            if a.compile:
                row = compile_for_v5e(T, window)
            else:
                row = measure(T, window, a.reps)
                dev = jax.devices()[0]
                row.update(platform=dev.platform,
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

#!/usr/bin/env python3
"""Step 0 of PR 43: the one-step update of a Mamba layer's state ALONE, in
the loop a decode program runs it in: a `fori_loop` over the 26 rows of a
stacked, DONATED state `f32[26,256,16,5120]` (AI21-Jamba2-3B at 256 slots,
2.18 GB), each layer's streams hanging on the layer before (u bf16 [S, E],
delta f32 [S, E], B and C f32 [S, N], A = -exp(A_log[l]) and D[l] out of
stacked bf16 weights, as `models/jamba.py::mamba_mixer` hands them). Ways:

  xla            today's spelling: the row sliced out of the stack,
                 `selective_step_xla`, `dynamic_update_index_in_dim` back
  pallas:BSxBE   `ops/selective_scan.py::selective_step_pallas` on the stack
                 and the index, blocks of BS slots x BE channels
  xla_y_after    y from the NEW row read back out of the updated stack
  xla_rows       h' and y as one array [S, N + 1, E], split after
  gate           what `ops/autobench.prefer` draws for the key, this run

The floor is two passes of the stack, 2 x 2.18 GB: 5.9-6.3 ms at the
690-740 GB/s this chip's large fusions reach (5.3 at the 819 GB/s peak).
Wall time of chained, donated calls under one sync, ms a loop (a loop is 6
to 10 ms, a dispatch 0.2). Nothing here ships.

    python3 scripts/ssm_step_step0.py --out chiprun_out/pr43/step0.json   # on the chip
    JAX_PLATFORMS=cpu python3 scripts/ssm_step_step0.py --compile         # here: the chip's compiler, no chip
    JAX_PLATFORMS=cpu python3 scripts/ssm_step_step0.py --rehearse        # here: tiny, the interpreter, results compared
"""
from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp

from paddle_tpu.ops import autobench, selective_scan as ss

f32, bf16 = jnp.float32, jnp.bfloat16


def shapes(L, S, N, E):
    """The loop's arguments after the stack: name -> (shape, dtype)."""
    return {"u": ((S, E), bf16), "dt": ((S, E), f32), "B": ((S, N), f32),
            "C": ((S, N), f32), "A_log": ((L, N, E), bf16),
            "D": ((L, E), bf16)}


def make_args(L, S, N, E, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    a = jnp.log(jnp.arange(1, N + 1, dtype=f32))
    return (0.1 * jax.random.normal(ks[0], (L, S, N, E), f32),
            jax.random.normal(ks[1], (S, E), f32).astype(bf16),
            0.05 * jax.random.uniform(ks[2], (S, E), f32),
            jax.random.normal(ks[3], (S, N), f32),
            jax.random.normal(ks[4], (S, N), f32),
            jnp.broadcast_to(a[None, :, None], (L, N, E)).astype(bf16),
            (1.0 + 0.1 * jax.random.normal(ks[5], (L, E), f32)).astype(bf16))


def _at(a, l):
    return jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False)


def _put(stack, row, l):
    return jax.lax.dynamic_update_index_in_dim(stack, row, l, 0)


def _xla(u, dt, A, B, C, D, stack, l):
    y, h = ss.selective_step_xla(u, dt, A, B, C, D, _at(stack, l))
    return y, _put(stack, h, l)


def _xla_y_after(u, dt, A, B, C, D, stack, l):
    uf = u.astype(f32)
    h, _ = ss._one_token(_at(stack, l), uf, dt, A, B, C)
    stack = _put(stack, h, l)
    y = jnp.sum(_at(stack, l) * C[:, :, None], axis=1)
    return (y + D.astype(f32) * uf).astype(u.dtype), stack


def _xla_rows(u, dt, A, B, C, D, stack, l):
    uf = u.astype(f32)
    h, y = ss._one_token(_at(stack, l), uf, dt, A, B, C)
    both = jnp.concatenate([h, y[:, None]], axis=1)     # [S, N + 1, E]
    N = h.shape[1]
    return (both[:, N] + D.astype(f32) * uf).astype(u.dtype), \
        _put(stack, both[:, :N], l)


def _pallas(block):
    def step(u, dt, A, B, C, D, stack, l):
        return ss.selective_step_pallas(u, dt, A, B, C, D, stack, l,
                                        block=block, interpret=INTERPRET)
    return step


def _gate(u, dt, A, B, C, D, stack, l):
    y, h = ss.selective_step(u, dt, A, B, C, D, ss.StackedRow(stack, l))
    return y, h.stack


INTERPRET = None
WAYS = {"xla": _xla, "xla_y_after": _xla_y_after, "xla_rows": _xla_rows,
        "gate": _gate}


def way(name):
    if name.startswith("pallas:"):
        bs, be = name[7:].split("x")
        return _pallas((int(bs), int(be)))
    return WAYS[name]


def loop_of(step):
    """(stack, streams) -> (stack, y of the last layer): every layer's u
    hangs on the layer before, so nothing leaves the loop."""
    def run(stack, u, dt, B, C, A_log, D):
        def layer(l, carry):
            stack, y = carry
            A = -jnp.exp(_at(A_log, l).astype(f32))
            y, stack = step(u + (y * 1e-3).astype(u.dtype), dt, A, B, C,
                            _at(D, l), stack, l)
            return stack, y
        return jax.lax.fori_loop(0, stack.shape[0], layer,
                                 (stack, jnp.zeros_like(u)))
    return run


def ops_naming(text, state):
    """The fusions, custom calls and copies of a compiled module's text
    whose result, or whose fused computation's parameter, is of a type
    that `state` (a compiled pattern) finds: name -> how often."""
    reads = {m.group(1) for m in re.finditer(
        r"^(%fused_computation[\w.\-]*) \(([^)]*)\) ->", text, re.M)
        if state.search(m.group(2))}
    found = {}
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*?) "
                     r"(fusion|custom-call|copy)\(", ln)
        if not m:
            continue
        called = re.search(r"calls=(%[\w.\-]+)", ln)
        if state.search(m.group(2)) or (called and called.group(1) in reads):
            found[m.group(1)] = found.get(m.group(1), 0) + 1
    return found


def compile_for_v5e(names, L, S, N, E, text_dir):
    """Each way's loop through the chip's compiler for a described v5e:
    what Mosaic refuses, whether the stack stays one buffer, and which
    operations of the loop's body name the state."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    ss.on_tpu = lambda: True
    ss._auto_step_impl = lambda *a: "pallas"
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    spec = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=chip)  # noqa: E731
    args = [spec((L, S, N, E), f32)] + [spec(*v)
                                        for v in shapes(L, S, N, E).values()]
    state = re.compile(rf"f32\[(?:{L},)?{S},{N},{E}\]")
    out = {}
    for name in names:
        t0 = time.perf_counter()
        try:
            c = jax.jit(loop_of(way(name)), donate_argnums=0).lower(
                *args).compile()
        except Exception as e:
            out[name] = {"refused": str(e)[:600]}
            print(f"{name}: REFUSED {str(e)[:600]}", flush=True)
            continue
        m, text = c.memory_analysis(), c.as_text()
        ops = ops_naming(text, state)
        out[name] = {"compile_s": round(time.perf_counter() - t0, 1),
                     "temporaries_mb": round(m.temp_size_in_bytes / 1e6, 1),
                     "aliased_gb": round(m.alias_size_in_bytes / 1e9, 3),
                     "ops_naming_the_state": ops}
        print(f"{name}: {out[name]}", flush=True)
        if text_dir:
            os.makedirs(text_dir, exist_ok=True)
            with open(os.path.join(
                    text_dir, re.sub(r"\W", "_", name) + ".hlo.txt"),
                    "w") as f:
                f.write(text)
    return out


def time_on_the_chip(names, L, S, N, E, reps, chain):
    """ms a loop, the median of `reps` chains of `chain` donated calls,
    and each way's widest difference from `xla` after one loop."""
    rows, want = {}, None
    for name in names:
        try:
            fn = jax.jit(loop_of(way(name)), donate_argnums=0)
            stack, *streams = make_args(L, S, N, E)
            stack, y = fn(stack, *streams)
            got = (jax.device_get(y).astype("float32"),
                   jax.device_get(stack[L - 1, :4]))
            if name == "xla":
                want = got
            samples = []
            for _ in range(reps):
                t0 = time.perf_counter()
                for _ in range(chain):
                    stack, y = fn(stack, *streams)
                jax.block_until_ready((stack, y))
                samples.append((time.perf_counter() - t0) / chain * 1e3)
            rows[name] = {"ms_a_loop": round(statistics.median(samples), 3),
                          "ms_min_max": [round(min(samples), 3),
                                         round(max(samples), 3)]}
            if want is not None:
                rows[name]["widest_gap_y_h"] = [
                    float(abs(g - w).max()) for g, w in zip(got, want)]
            del stack, y
        except Exception as e:
            rows[name] = {"error": f"{type(e).__name__}: {e}"[:600]}
        print(f"{name}: {rows[name]}", flush=True)
    return rows


def main():
    global INTERPRET
    ap = argparse.ArgumentParser()
    ap.add_argument("--ways", default="xla,pallas:8x5120,pallas:16x2560,"
                    "pallas:8x2560,pallas:16x5120,xla_y_after,xla_rows,gate")
    ap.add_argument("--layers", type=int, default=26)
    ap.add_argument("--slots", type=int, default=256)
    ap.add_argument("--state", type=int, default=16)
    ap.add_argument("--channels", type=int, default=5120)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--chain", type=int, default=10)
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--text", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    names = args.ways.split(",")
    dims = (args.layers, args.slots, args.state, args.channels)
    if args.rehearse:
        INTERPRET = True
        dims = (3, 16, 8, 512)
        names = ["xla", "pallas:8x512", "pallas:8x256", "pallas:16x512",
                 "xla_y_after", "xla_rows", "gate"]
        args.reps, args.chain = 1, 1
    if args.compile:
        rows = compile_for_v5e(names, *dims, args.text)
    else:
        dev = jax.devices()[0]
        if not args.rehearse and dev.platform != "tpu":
            sys.exit("needs a TPU (or --compile / --rehearse)")
        rows = time_on_the_chip(names, *dims, args.reps, args.chain)
        rows = {"device": {"platform": dev.platform,
                           "kind": dev.device_kind},
                "rehearsal": args.rehearse, "shape": dims,
                "gate": {str(k): v
                         for k, v in autobench.decisions().items()},
                "ways": rows}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    print(json.dumps(rows))


if __name__ == "__main__":
    main()

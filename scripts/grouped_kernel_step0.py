#!/usr/bin/env python3
"""Step 0 of the grouped-query paged kernel (PR 41): where the time of
`ops/paged_attention.py::_grouped_kernel` goes, at the two shapes that run
it: Trinity-Mini's full layer (64 slots, 32 query heads over 4 KV heads of
128, pages of 64, K and V pools, the contexts of the cell's mix:
`scripts/window_kernel_step0.py`) and LFM2's (64 slots, 32 over 8 of 64,
pages of 16, one fused pool, the cell's live share ragged:
`scripts/paged_kernel_step0.py`). One kernel body a reading, each put in
the place of `_grouped_kernel` under the same wrapper, pools, table and
page walk:

  vpu         the kernel of before PR 41: a page at a time, its products on
              the VPU in float32 once a head group (`_softmax_update`)
  copies      the page copies alone, no arithmetic
  read_words  + every KV head of every block read out as rows [B ps, w] by
  read_index    `_head_rows`, in its two spellings (the strided read of
                32-bit words; the indexed head), its largest row kept
  mxu_words   + the two products on the MXU and the softmax: the kernel,
  mxu_index     in both spellings of the read
  tree        `paged_attention_pallas` as the tree has it
  xla         the gather path

Milliseconds a call: `--calls` calls chained in one jitted program (each
call's output feeds the next call's query), median of `--reps`, over the
calls; beside them the model's bytes at the HBM's peak and, for the whole
kernels, the widest difference from the XLA path. From a tree's root, on
the chip; `--compile` here gives Mosaic's verdict on each body at the real
shapes with no chip, `--rehearse` runs tiny shapes in interpret mode (no
number of either is a device metric).

    chiprun -- python3 scripts/grouped_kernel_step0.py --out chiprun_out/pr41/step0.json
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
from jax.experimental import pallas as pl  # noqa: E402

from benchmark.lib import peaks  # noqa: E402
from paddle_tpu.ops import paged_attention as pa  # noqa: E402

_GROUPED_KERNEL = pa._grouped_kernel
_HBM_BYTES_S = peaks.peak("TPU v5 lite")["hbm_bytes_s"]


def body(mode):
    """A kernel with `_grouped_kernel`'s signature that does `mode`'s part
    of the work."""
    kind, _, how = mode.partition("_")
    if kind == "mxu":
        return lambda *refs, words, **kw: _GROUPED_KERNEL(
            *refs, words=how == "words", **kw)

    def kernel(pt_ref, len_ref, ly_ref, q_ref, *refs, page_size, scale,
               block, words):
        ps, B = page_size, block
        n_pools = (len(refs) - 3) // 2
        pools, o_ref = refs[:n_pools], refs[n_pools]
        bufs, (sem, first_ref) = refs[n_pools + 1:-2], refs[-2:]
        _, G, Hkv, w = q_ref.shape
        zero = jnp.zeros((Hkv, 1), jnp.float32)
        acc0 = jnp.zeros((Hkv, w), jnp.float32)
        walk = lambda on_block, carry: pa._walk_blocks(
            pt_ref, len_ref, ly_ref[0], pools, bufs, sem, first_ref, ps, B,
            on_block, carry)

        if kind == "vpu":       # the parent's `_paged_kernel`, G groups
            q = [q_ref[0, g].astype(jnp.float32) * scale for g in range(G)]

            def page_of(b, j):
                k = bufs[0][b, j].astype(jnp.float32)
                return k, (k if n_pools == 1
                           else bufs[1][b, j].astype(jnp.float32))

            def update(carry, k, v, live=None):
                return tuple(pa._softmax_update(c, qg, k, v, live)
                             for c, qg in zip(carry, q))

            carry, n, n_blocks, b0 = walk(
                lambda i, b, n, carry: jax.lax.fori_loop(
                    0, jnp.minimum(B, n - 1 - i * B),
                    lambda j, c: update(c, *page_of(b, j)), carry),
                ((zero + pa._NEG, zero, acc0),) * G)
            last = n_blocks - 1
            idx = (n - 1) * ps + jax.lax.broadcasted_iota(
                jnp.int32, (ps, Hkv, 1), 0)
            carry = update(carry, *page_of((b0 + last) % 2, n - 1 - last * B),
                           live=idx < len_ref[pl.program_id(0)])
            for g, (_, l, acc) in enumerate(carry):
                o_ref[0, g] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(
                    o_ref.dtype)
        elif kind == "copies":
            _, _n, n_blocks, b0 = walk(lambda i, b, n, carry: carry, acc0)
            o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)
        else:                   # read: every head of every block
            def on_block(i, b, n, carry):
                for buf in bufs:
                    carry += jnp.concatenate(
                        [jnp.max(pa._head_rows(buf.at[b], h, how == "words")
                                 .astype(jnp.float32), axis=0, keepdims=True)
                         for h in range(Hkv)], axis=0)
                return carry
            acc, _n, n_blocks, b0 = walk(on_block, acc0)
            for g in range(G):
                o_ref[0, g] = acc.astype(o_ref.dtype)
        first_ref[0] = (b0 + n_blocks) % 2
    return kernel


def shapes(rehearse):
    """name -> (q, pools, table, ctx): the two calls' arguments."""
    from scripts.paged_kernel_step0 import CELLS, _case
    from scripts.window_kernel_step0 import contexts
    key = jax.random.PRNGKey(0)
    rng = np.random.RandomState(1)
    out = {}
    if rehearse:
        tr = dict(S=4, H=8, Hkv=2, d=128, ps=8, P=60, M=12)
        ctx = np.asarray([3, 9, 64, 90], np.int32)
        lf = dict(S=4, H=8, Hkv=4, d=64, ps=8, M=12, P=60, L=1, stack=1,
                  fused=True, live=0.5)
    else:
        tr = dict(S=64, H=32, Hkv=4, d=128, ps=64, P=7680, M=288)
        ctx = contexts(64, 0, 256, 18000)
        lf = CELLS["lfm2_8b_a1b_serve.decode_closed128"]
    dt = jnp.bfloat16
    shape = (1, tr["P"] + 1, tr["ps"], tr["Hkv"], tr["d"])
    out["trinity_full"] = (
        jax.random.normal(key, (tr["S"], tr["H"], tr["d"]), dt),
        (jax.random.normal(jax.random.fold_in(key, 1), shape, dt),
         jax.random.normal(jax.random.fold_in(key, 2), shape, dt)),
        jnp.asarray(rng.randint(0, tr["P"], (tr["S"], tr["M"])), jnp.int32),
        jnp.asarray(ctx))
    live = max(1, int(round(lf["live"] * lf["M"] * lf["ps"])))
    table, lens = _case(lf, rng.randint(1, 2 * live, lf["S"]), rng)
    out["lfm2_fused"] = (
        jax.random.normal(key, (lf["S"], lf["H"], lf["d"]), dt),
        (jax.random.normal(jax.random.fold_in(key, 3),
                           (1, lf["P"] + 1, lf["ps"], lf["Hkv"],
                            2 * lf["d"]), dt), None),
        table, lens)
    return out


def chained(mode, calls, interpret):
    """`calls` calls of one implementation as one jitted program."""
    def one(q, pools, table, ctx):
        if mode == "xla":
            return pa.paged_attention_xla(q, *pools, table, ctx, layer=0)
        return pa.paged_attention_pallas(q, *pools, table, ctx, layer=0,
                                         interpret=interpret)

    @jax.jit
    def run(q, pools, table, ctx):
        def step(q, _):
            return (q + one(q, pools, table, ctx) * 1e-3).astype(q.dtype), None
        return jax.lax.scan(step, q, None, length=calls)[0]
    return jax.jit(one), run


MODES = ("vpu", "copies", "read_words", "read_index", "mxu_words",
         "mxu_index", "tree", "xla")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--mode", action="append", choices=MODES)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--compile", action="store_true")
    a = ap.parse_args()
    if a.compile:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        one_chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif not a.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU: a kernel's time is a chip reading")
    rows = {"rehearsal": a.rehearse, "compile_only": a.compile,
            "device": jax.devices()[0].device_kind, "calls": a.calls}
    for name, args in shapes(a.rehearse).items():
        q, pools, table, ctx = args
        need = peaks.paged_attention_bytes(int(np.asarray(ctx).sum()),
                                           pools[0].shape[3], q.shape[2])
        rows[name] = {"ctx_mean": float(np.asarray(ctx).mean()),
                      "need_ms_at_peak": 1e3 * need / _HBM_BYTES_S}
        want = None if a.compile else np.asarray(
            pa.paged_attention_xla(q, *pools, table, ctx, layer=0),
            np.float32)
        for mode in a.mode or MODES:
            pa._grouped_kernel = _GROUPED_KERNEL if mode in ("tree", "xla") \
                else body(mode)
            one, run = chained(mode, 1 if a.rehearse else a.calls, a.rehearse)
            try:
                if a.compile:
                    run.lower(*jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(
                            x.shape, x.dtype, sharding=one_chip),
                        args)).compile()
                    rows[name][mode] = "compiles"
                    continue
                got = np.asarray(jax.block_until_ready(one(*args)),
                                 np.float32)
                jax.block_until_ready(run(*args))
                ts = []
                for _ in range(1 if a.rehearse else a.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(*args))
                    ts.append(time.perf_counter() - t0)
            except Exception as e:      # Mosaic's refusal
                rows[name][mode] = f"FAILED {str(e)[:400]}"
                print(name, mode, rows[name][mode], flush=True)
                continue
            ms = 1e3 * float(np.median(ts)) / (1 if a.rehearse else a.calls)
            rows[name][mode] = {
                "ms": ms,
                "roofline_pct": 100 * rows[name]["need_ms_at_peak"] / ms}
            if mode.split("_")[0] in ("vpu", "mxu", "tree"):
                rows[name][mode]["widest_difference_from_xla"] = float(
                    np.max(np.abs(got - want)))
            print(name, mode, rows[name][mode], flush=True)
    pa._grouped_kernel = _GROUPED_KERNEL
    print(json.dumps(rows))
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()

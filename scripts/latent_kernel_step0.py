#!/usr/bin/env python3
"""Step 0 of PR 32: the latent paged-attention kernel ALONE, at the shapes
of `kanana2_30b_a3b_serve.longdoc_closed128` (64 slots, 32 query heads on
ONE cached row of 576 = [c 512 | kr 64] a token, pages of 64, 6,400 pages,
the cell's live contexts: ~321,000 tokens), one layer a call. It settles
how the 576 lie in a page. Candidates:

  row640   [L, P, ps, 640]: the row padded to five registers' lanes; one
           page copy, the value is lanes 0..511 of the key's own row.
           (`ops/paged_attention.py`'s kernel.)
  split    two parts, c [L, P, ps, 512] and kr packed two tokens a row
           [L, P, ps/2, 128] (token r | token r + ps/2: a part 64 lanes
           wide is padded to 128 by the device, which is row640 again).
           Tight: 1,152 B a token. Two page copies, two half-page
           products, the tokens of a block taken in the order (half,
           page, row): softmax does not mind.
  flat     a page's tokens side by side in one minor dimension
           [L, P, ps x 576], as PR 26 laid the routing part; the kernel
           must turn [ps x 576] into [ps, 576] itself.
  row576   [L, P, ps, 576] as it is, no padding (what Mosaic says to it).

For each: what the chip's compiler makes of the pool (bytes), whether
Mosaic takes the kernel, ms a call (one layer) and x 7 a decode step,
against the live rows' bytes (1,152 B a token) at the HBM peak, and
against `paged_latent_attention_xla` on row640. `--compile` does the
first two here, for a described v5e, with no chip.

    python3 scripts/latent_kernel_step0.py            # on the chip
    JAX_PLATFORMS=cpu python3 scripts/latent_kernel_step0.py --compile
    JAX_PLATFORMS=cpu python3 scripts/latent_kernel_step0.py --rehearse
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from benchmark.lib import traffic as traffic_lib
from paddle_tpu.ops import paged_attention as pa

HBM = 819e9
C, R = 512, 64              # kv_lora_rank, qk_rope_head_dim
W = C + R


def cell_contexts(S, seed=0):
    """Live contexts of S slots drawn as the cell's: a prompt of the
    traffic's multiset plus a uniform share of its output."""
    tr = traffic_lib.load(traffic_lib.find(
        os.path.join(os.path.dirname(__file__), "..", "benchmark"),
        "traffic", "longdoc_closed128"))
    items = traffic_lib.epoch(tr)
    rng = np.random.RandomState(seed)
    pick = rng.permutation(len(items))[:S]
    return np.asarray([items[i]["prompt_len"]
                       + int(rng.rand() * items[i]["max_new"])
                       for i in pick], np.int32)


# -- the candidates' kernels beside the module's ---------------------------

def _split_kernel(pt_ref, len_ref, ly_ref, q_ref, c_ref, k_ref, o_ref, cbuf,
                  kbuf, sem, first_ref, *, page_size, scale, block):
    ps, B, hp = page_size, block, page_size // 2
    s = pl.program_id(0)
    q = q_ref[0]                                   # [H, C + 256]
    H = q.shape[0]
    qc, qr = q[:, :C], (q[:, C:C + 128], q[:, C + 128:])
    T = B * hp
    ctx = len_ref[s]

    def on_block(i, b, n, carry):
        last = (i + 1) * B >= n
        kr = kbuf[b].reshape(T, 128)
        c = cbuf[b]
        for h in (0, 1):
            m_prev, l_prev, acc = carry
            ch = c[:, h * hp:(h + 1) * hp].reshape(T, C)

            def idx(shape, axis):
                x = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
                return (i * B + x // hp) * ps + h * hp + x % hp
            live_col = idx((T, 1), 0) < ctx
            zero = lambda r: jnp.where(live_col, r, jnp.zeros_like(r))
            ch = jax.lax.cond(last, zero, lambda r: r, ch)
            krh = jax.lax.cond(last, zero, lambda r: r, kr)
            nt = (((1,), (1,)), ((), ()))
            sc = (jax.lax.dot_general(qc, ch, nt,
                                      preferred_element_type=jnp.float32)
                  + jax.lax.dot_general(qr[h], krh, nt,
                                        preferred_element_type=jnp.float32)
                  ) * scale
            sc = jnp.where(idx((1, T), 1) < ctx, sc, pa._NEG)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)
            # the second half of a block may be all dead (a context that
            # ends in a page's first half): exp(_NEG - _NEG) = 1
            p = jnp.where(idx((1, T), 1) < ctx, p, 0.0)
            carry = (m_new,
                     alpha * l_prev + jnp.sum(p, axis=1, keepdims=True),
                     acc * alpha + jnp.dot(p.astype(ch.dtype), ch,
                                           preferred_element_type=jnp.float32))
        return carry

    zero = jnp.zeros((H, 1), jnp.float32)
    (_, l, acc), _n, n_blocks, b0 = pa._walk_blocks(
        pt_ref, len_ref, ly_ref[0], (c_ref, k_ref), (cbuf, kbuf), sem,
        first_ref, ps, B, on_block,
        (zero + pa._NEG, zero, jnp.zeros((H, C), jnp.float32)))
    first_ref[0] = (b0 + n_blocks) % 2
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def split_call(q, c_pool, k_pool, pt, ctx, scale, layer, interpret=False):
    S, H, _ = q.shape
    ps = c_pool.shape[2]
    z = jnp.zeros((S, H, R), q.dtype)
    q = jnp.concatenate([q[..., :C], q[..., C:], z, z, q[..., C:]], -1)
    B = pa._block_pages(ps * W * 2, 1, pt.shape[1])
    B -= B % 2                      # B ps / 2 a multiple of the bf16 tile
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(S,),
        in_specs=[pl.BlockSpec((1, H, C + 256), lambda s, *_: (s, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, C), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, B, ps, C), c_pool.dtype),
                        pltpu.VMEM((2, B, ps // 2, 128), k_pool.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32)])
    return pl.pallas_call(
        functools.partial(_split_kernel, page_size=ps, scale=float(scale),
                          block=B),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, C), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pt, ctx, jnp.asarray(layer, jnp.int32).reshape(1), q, c_pool, k_pool)


def flat_call(q, pool, pt, ctx, scale, layer, ps, interpret=False):
    S, H, _ = q.shape
    B = pa._block_pages(ps * W * 2, 1, pt.shape[1])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(S,),
        in_specs=[pl.BlockSpec((1, H, W), lambda s, *_: (s, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, C), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, B, ps * W), pool.dtype),
                        pltpu.SemaphoreType.DMA((1, 2)),
                        pltpu.SMEM((1,), jnp.int32)])
    return pl.pallas_call(
        # the module's kernel: its `buf[b].reshape(B ps, W)` is here the
        # turn of [B, ps x 576] into rows that Mosaic has to make
        functools.partial(pa._latent_kernel, page_size=ps,
                          scale=float(scale), block=B, value_width=C),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, C), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pt, ctx, jnp.asarray(layer, jnp.int32).reshape(1), q, pool)


# -- pools of each layout from one set of rows ------------------------------

def pack_split(rows):
    """rows [L, P, ps, 576] -> (c [L, P, ps, 512], kr [L, P, ps/2, 128])."""
    L, P, ps, _ = rows.shape
    kr = rows[..., C:].reshape(L, P, 2, ps // 2, R)
    return rows[..., :C], jnp.concatenate([kr[:, :, 0], kr[:, :, 1]], -1)


def candidates(scale, ps, interpret):
    xla = lambda q, pool, pt, ctx, l: pa.paged_latent_attention_xla(
        q, pool, pt, ctx, C, scale, l)
    row = lambda q, pool, pt, ctx, l: pa.paged_latent_attention_pallas(
        q, pool, pt, ctx, C, scale, l, interpret=interpret)
    return {
        "row640": (lambda rows: (jnp.pad(
            rows, ((0, 0),) * 3 + ((0, 640 - W),)),), row),
        "row640_xla": (lambda rows: (jnp.pad(
            rows, ((0, 0),) * 3 + ((0, 640 - W),)),), xla),
        "split": (pack_split, lambda q, c, k, pt, ctx, l: split_call(
            q, c, k, pt, ctx, scale, l, interpret)),
        "flat": (lambda rows: (rows.reshape(rows.shape[:2] + (-1,)),),
                 lambda q, pool, pt, ctx, l: flat_call(
                     q, pool, pt, ctx, scale, l, ps, interpret)),
        "row576": (lambda rows: (rows,), row),
    }


def shapes_of(make, L, P, ps):
    out = jax.eval_shape(make, jax.ShapeDtypeStruct((L, P, ps, W),
                                                    jnp.bfloat16))
    return tuple(out)


def compile_only(S, H, L, P, ps, M):
    """What the chip's compiler says, with no chip: the pools' bytes on
    the device and whether Mosaic takes each kernel."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    scale = 1 / math.sqrt(192)
    for name, (make, fn) in candidates(scale, ps, False).items():
        pools = [sds(p.shape, p.dtype) for p in shapes_of(make, L, P, ps)]
        args = (sds((S, H, W), jnp.bfloat16), *pools,
                sds((S, M), jnp.int32), sds((S,), jnp.int32),
                sds((), jnp.int32))
        row = {"layout": name, "pool_shapes": [p.shape for p in pools],
               "logical_bytes": sum(math.prod(p.shape) * 2 for p in pools)}
        try:
            c = jax.jit(fn).lower(*args).compile()
            m = c.memory_analysis()
            row.update(compiles=True,
                       argument_bytes=int(m.argument_size_in_bytes),
                       temp_bytes=int(m.temp_size_in_bytes))
        except Exception as e:              # Mosaic's or XLA's refusal
            row.update(compiles=False, error=str(e).splitlines()[0][:300])
        print(json.dumps(row), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default="chiprun_out/latent_step0.json")
    a = ap.parse_args()
    S, H, L, P, ps, M = 64, 32, 7, 6401, 64, 160
    if a.compile:
        return compile_only(S, H, L, P, ps, M)
    if a.rehearse:
        S, H, L, P, ps, M = 4, 4, 2, 41, 16, 160
    ctx = cell_contexts(S)
    if a.rehearse:
        ctx = np.minimum(ctx // 40 + 1, M * ps).astype(np.int32)
    rng = np.random.RandomState(0)
    # each slot its own pages, scattered over the pool as a run leaves them
    need = -(-ctx // ps)
    perm = rng.permutation(P - 1)
    pt = np.full((S, M), P - 1, np.int32)
    at = 0
    for s_, n in enumerate(need):
        pt[s_, :n] = perm[(at + np.arange(n)) % (P - 1)]
        at += int(n)
    key = jax.random.PRNGKey(0)
    rows = jax.random.normal(key, (L, P, ps, W), jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 1), (S, H, W), jnp.bfloat16)
    pt, ctxd = jnp.asarray(pt), jnp.asarray(ctx)
    scale = 1 / math.sqrt(192)
    live = int(ctx.sum())
    floor_ms = live * W * 2 / HBM * 1e3
    print(json.dumps({"slots": S, "live_tokens": live, "pages_live":
                      int(need.sum()), "roofline_ms_a_layer": floor_ms,
                      "device": str(jax.devices()[0])}), flush=True)
    interpret = jax.devices()[0].platform != "tpu"
    ref, out = None, []
    for name, (make, fn) in candidates(scale, ps, interpret).items():
        row = {"layout": name}
        try:
            pools = jax.block_until_ready(jax.jit(make)(rows))
            row["pool_bytes_a_token"] = sum(
                p.nbytes for p in pools) / (L * P * ps)
            f = jax.jit(fn)
            o = jax.block_until_ready(f(q, *pools, pt, ctxd, jnp.int32(0)))
            if ref is None:
                ref = o.astype(jnp.float32)
            row["max_diff_from_row640"] = float(
                jnp.max(jnp.abs(o.astype(jnp.float32) - ref)))
            t0 = time.perf_counter()
            for r in range(a.reps):
                o = f(q, *pools, pt, ctxd, jnp.int32(r % L))
            jax.block_until_ready(o)
            ms = (time.perf_counter() - t0) / a.reps * 1e3
            row.update(ms_a_layer=ms, ms_a_step_7_layers=7 * ms,
                       share_of_roofline=floor_ms / ms)
            del pools
        except Exception as e:
            row.update(failed=str(e).splitlines()[0][:300])
        out.append(row)
        print(json.dumps(row), flush=True)
    if not a.rehearse:
        os.makedirs(os.path.dirname(a.out), exist_ok=True)
        with open(a.out, "w") as f_:
            json.dump(out, f_, indent=1)


if __name__ == "__main__":
    main()

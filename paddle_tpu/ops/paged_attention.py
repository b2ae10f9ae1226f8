"""Ragged paged-attention decode (PAPERS.md: Ragged Paged Attention).

The serving tier stores each request's KV history in fixed-size pages of
a preallocated HBM pool; decode computes one new token per in-flight
request ("slot") against its own ragged-length history. Two
implementations behind one function:

  * gather-based XLA: k_pages[layer, page_table] gathers each slot's
    pages into a [S, M*ps] context, masked past ctx_len — one fused XLA
    computation, the portable default;
  * a Pallas TPU kernel: grid (slot, page), layer and page indices
    scalar-prefetched so each program DMAs exactly one page from HBM,
    online-softmax accumulation in VMEM scratch, the per-head mat-vecs
    on the VPU.

Selection runs through ops/autobench.prefer — the same measure-once gate
that arbitrates Pallas-vs-XLA flash attention — so the hand kernel only
holds the hot path on shapes where it measures faster.

Layouts (H query heads over Hkv key/value heads, H = G x Hkv; KV head j
serves query heads G*j .. G*j+G-1; G = 1 is plain multi-head attention):
  q          [S, H, d]        one query token per slot
  k/v_pages  [P, ps, Hkv, d]  the page pools of one layer, or stacked
             [L, P, ps, Hkv, d] over layers with
  layer      int32 scalar     which layer's pages to read (may be traced)
  page_table [S, M] int32     pool index of each slot's m-th page
  ctx_lens   [S] int32        valid history length per slot (>= 1)
Returns     [S, H, d]

A head size under the 128 lanes of a TPU register makes a poor minor
dimension: the device pads it or turns the pool round so that pages lie
across lanes (looked at with the chip's compiler, PR 26). Such a model
keeps ONE fused pool `[L, P, ps, Hkv, 2d]`, K in the first d lanes of a
head and V in the last d, and passes it as `k_pages` with `v_pages=None`:
one gather, or one page DMA, brings both.

The two pool ranks are one algorithm: both implementations address
(layer, page) in the pool they are given, and a rank-4 pool is a stacked
one of a single layer. A body that scans over layers with the stacked
cache in its carry passes the stack and its index, never `ck[l]`: a
traced slice in front of a kernel that takes whole arrays is a copy of
that layer's pool, every layer, every step (docs/KERNELS.md).
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..fluid.registry import register, same_shape_as
from ..fluid.ops.common import x
from .pallas_attention import on_tpu

__all__ = ["paged_attention_decode", "paged_attention_xla",
           "paged_attention_pallas"]

_NEG = -1e30


def _stacked(k_pages, v_pages, layer):
    """(k, v, layer) in the stacked form [L, P, ps, H, d] with an int32
    scalar layer; a rank-4 pool is the stack of its one layer."""
    if k_pages.ndim == 4:
        if layer is not None:
            raise ValueError("layer indexes a stacked pool "
                             "[L, P, ps, H, d]; this pool has rank 4")
        k_pages, layer = k_pages[None], 0
        v_pages = None if v_pages is None else v_pages[None]
    elif layer is None:
        raise ValueError("a stacked pool [L, P, ps, H, d] needs layer")
    elif isinstance(layer, int) and not 0 <= layer < k_pages.shape[0]:
        # (a traced layer cannot be checked: the kernel's DMA would read
        # past the pool, the gather would clamp)
        raise ValueError(f"layer {layer} of a pool of {k_pages.shape[0]}")
    return k_pages, v_pages, jnp.asarray(layer, jnp.int32)


def paged_attention_xla(q, k_pages, v_pages, page_table, ctx_lens,
                        scale=None, layer=None):
    """Gather-based reference path; fully fused by XLA."""
    S, H, d = q.shape
    k_pages, v_pages, layer = _stacked(k_pages, v_pages, layer)
    ps = k_pages.shape[2]
    M = page_table.shape[1]
    Hkv, G = k_pages.shape[3], _groups(q, k_pages)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if v_pages is None:
        kv = k_pages[layer, page_table].reshape(S, M * ps, Hkv, 2 * d)
        k, v = kv[..., :d], kv[..., d:]
    else:
        k = k_pages[layer, page_table].reshape(S, M * ps, Hkv, d)
        v = v_pages[layer, page_table].reshape(S, M * ps, Hkv, d)
    if G == 1:      # multi-head: the program it has always been
        logits = jnp.einsum("shd,sthd->sht", q, k,
                            preferred_element_type=jnp.float32) * scale
        pos = jnp.arange(M * ps, dtype=jnp.int32)[None, :]
        logits = jnp.where(pos[:, None, :] < ctx_lens[:, None, None],
                           logits, _NEG)
        probs = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("sht,sthd->shd", probs.astype(v.dtype), v)
        return o.astype(q.dtype)
    # the heads of one KV head side by side: [S, Hkv * G, d] in memory is
    # already [S, Hkv, G, d]
    logits = jnp.einsum("skgd,stkd->skgt", q.reshape(S, Hkv, G, d), k,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(M * ps, dtype=jnp.int32)[None, :]
    logits = jnp.where(pos[:, None, None, :] < ctx_lens[:, None, None, None],
                       logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("skgt,stkd->skgd", probs.astype(v.dtype), v)
    return o.reshape(S, H, d).astype(q.dtype)


def _groups(q, k_pages) -> int:
    """Query heads per KV head."""
    H, Hkv = q.shape[1], k_pages.shape[-2]
    if H % Hkv:
        raise ValueError(f"{H} query heads over {Hkv} KV heads")
    return H // Hkv


# ---------------------------------------------------------------------------
# Pallas kernel: grid (slot, page); page_table, ctx_lens and the layer are
# scalar-prefetched so the k/v BlockSpec index_map can steer each program's
# DMA at one page of one layer.
# ---------------------------------------------------------------------------

def _paged_kernel(pt_ref, len_ref, ly_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, page_size, scale):
    s, m = pl.program_id(0), pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(m == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # one query token per head against one page: a batched mat-vec, done
    # on the VPU with the head axis kept in place. (The MXU spelling,
    # einsum("hd,phd->hp"), puts the batch dimension in the middle of the
    # rhs and leaves the lhs no free dimension; Mosaic refuses it.)
    # Scores stay [ps, H, 1] so that they broadcast over d without a
    # relayout and reduce over the page axis into the [H, 1] scratch.
    q = q_ref[0].astype(jnp.float32)            # [H, d]
    k = k_ref[0, 0].astype(jnp.float32)         # [ps, H, d]
    scores = jnp.sum(q[None] * k, axis=-1, keepdims=True) * scale
    idx = m * page_size + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 0)
    live = idx < len_ref[s]
    scores = jnp.where(live, scores, _NEG)

    m_prev = m_ref[...]                          # [H, 1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=0))
    alpha = jnp.exp(m_prev - m_new)
    # masked again after exp: a dead page would give exp(_NEG - _NEG) = 1
    p = jnp.where(live, jnp.exp(scores - m_new[None]), 0.0)   # [ps, H, 1]
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0)
    v = v_ref[0, 0].astype(jnp.float32)          # [ps, H, d]
    acc_ref[...] = acc_ref[...] * alpha + jnp.sum(p * v, axis=0)
    m_ref[...] = m_new

    @pl.when(m == n_pages - 1)
    def _fin():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_kernel_gqa(pt_ref, len_ref, ly_ref, q_ref, *refs, page_size,
                      scale, groups, fused):
    """The kernel above for G query heads a KV head: q and o blocks are
    [1, G, Hkv, d] (group-major, the wrapper transposes), the scratch
    carries G online softmaxes, and one page of K and V, read once, serves
    all G of them. Each group's update is the multi-head kernel's.

    `fused`: one pool whose heads are [K | V] over 2d lanes. q arrives
    with zeros in the V lanes, so q . [K | V] is q . K; the accumulator
    sums p [K | V] and the wrapper keeps its V lanes. No lane is sliced
    in the kernel."""
    if fused:
        k_ref, o_ref, acc_ref, m_ref, l_ref = refs
        v_ref = k_ref
    else:
        k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    s, m = pl.program_id(0), pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(m == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # a page past the slot's context does no arithmetic (its table entry
    # is the trash page, so consecutive dead pages are not fetched again)
    @pl.when(m * page_size < len_ref[s])
    def _page():
        k = k_ref[0, 0].astype(jnp.float32)         # [ps, Hkv, d]
        v = k if fused else v_ref[0, 0].astype(jnp.float32)
        for g in range(groups):
            q = q_ref[0, g].astype(jnp.float32)     # [Hkv, d]
            scores = jnp.sum(q[None] * k, axis=-1, keepdims=True) * scale
            idx = m * page_size + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 0)
            live = idx < len_ref[s]
            scores = jnp.where(live, scores, _NEG)
            m_prev = m_ref[g]                        # [Hkv, 1]
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=0))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(live, jnp.exp(scores - m_new[None]), 0.0)
            l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=0)
            acc_ref[g] = acc_ref[g] * alpha + jnp.sum(p * v, axis=0)
            m_ref[g] = m_new

    @pl.when(m == n_pages - 1)
    def _fin():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_attention_pallas_gqa(q, k_pages, v_pages, page_table, ctx_lens,
                                scale, interpret, layer, G):
    S, H, d = q.shape
    ps, Hkv = k_pages.shape[2], k_pages.shape[3]
    M = page_table.shape[1]
    fused = v_pages is None
    w = k_pages.shape[4]                # d, or 2d of a fused pool
    page = pl.BlockSpec(
        (1, 1, ps, Hkv, w),
        lambda s, m, pt, ln, ly: (ly[0], pt[s, m], 0, 0, 0))
    heads = pl.BlockSpec((1, G, Hkv, w),
                         lambda s, m, pt, ln, ly: (s, 0, 0, 0))
    pools = (k_pages,) if fused else (k_pages, v_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, M),
        in_specs=[heads] + [page] * len(pools),
        out_specs=heads,
        scratch_shapes=[
            pltpu.VMEM((G, Hkv, w), jnp.float32),
            pltpu.VMEM((G, Hkv, 1), jnp.float32),
            pltpu.VMEM((G, Hkv, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel_gqa, page_size=ps,
                               scale=float(scale), groups=G, fused=fused)
    q = q.reshape(S, Hkv, G, d).transpose(0, 2, 1, 3)
    if fused:
        q = jnp.concatenate([q, jnp.zeros_like(q)], axis=-1)
    o = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, G, Hkv, w), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      layer.reshape(1), q, *pools)
    return o[..., w - d:].transpose(0, 2, 1, 3).reshape(S, H, d)


def paged_attention_pallas(q, k_pages, v_pages, page_table, ctx_lens,
                           scale=None, interpret=None, layer=None):
    S, H, d = q.shape
    k_pages, v_pages, layer = _stacked(k_pages, v_pages, layer)
    G = _groups(q, k_pages)
    if G > 1 or v_pages is None:
        return _paged_attention_pallas_gqa(
            q, k_pages, v_pages, page_table, ctx_lens,
            scale if scale is not None else 1.0 / math.sqrt(d),
            (not on_tpu()) if interpret is None else interpret, layer, G)
    ps = k_pages.shape[2]
    M = page_table.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = not on_tpu()
    page = pl.BlockSpec(
        (1, 1, ps, H, d),
        lambda s, m, pt, ln, ly: (ly[0], pt[s, m], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, M),
        in_specs=[
            pl.BlockSpec((1, H, d), lambda s, m, pt, ln, ly: (s, 0, 0)),
            page, page,
        ],
        out_specs=pl.BlockSpec((1, H, d),
                               lambda s, m, pt, ln, ly: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, d), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, page_size=ps,
                               scale=float(scale))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, d), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      layer.reshape(1), q, k_pages, v_pages)


def _gate_paged(S, H, d, P, ps, M, dtype, Hkv=None, fused=False):
    """(key, candidates, make_args) — shared by the decode-path gate and
    the autobench warm CLI (a fleet replica shipping a pre-warmed cache
    skips first-request measurement on its decode hot path).

    The candidates are timed in the form that runs, a stacked pool with
    the layer an argument of the jitted call, on a stack of ONE layer:
    neither candidate's time depends on L, and a serving engine's own
    pool leaves no room for a second one beside it. "stacked" in the key
    keeps a record measured on the rank-4 kernels of before from
    answering for these."""
    dtype = jnp.dtype(dtype)
    key = ("paged_attention", "stacked", S, H, d, P, ps, M, str(dtype))
    Hkv = H if Hkv is None else Hkv
    if Hkv != H or fused:   # other kernels, a key of their own
        key += ("kv_heads", Hkv) + (("fused",) if fused else ())

    def make_args():
        import numpy as np
        rng = np.random.RandomState(0)
        qq = jnp.asarray(rng.randn(S, H, d), dtype)
        kk = jnp.asarray(rng.randn(1, P, ps, Hkv, 2 * d if fused else d),
                         dtype)
        vv = None if fused else jnp.asarray(rng.randn(1, P, ps, Hkv, d),
                                            dtype)
        pt = jnp.asarray(rng.randint(0, P, (S, M)), jnp.int32)
        ln = jnp.asarray(rng.randint(1, M * ps + 1, (S,)), jnp.int32)
        return qq, kk, vv, pt, ln, jnp.zeros((), jnp.int32)

    def xla(qq, kk, vv, pt, ln, layer):
        return paged_attention_xla(qq, kk, vv, pt, ln, layer=layer)

    def pallas(qq, kk, vv, pt, ln, layer):
        return paged_attention_pallas(qq, kk, vv, pt, ln, interpret=False,
                                      layer=layer)

    return key, {"xla": xla, "pallas": pallas}, make_args


def _auto_impl(q, k_pages, page_table, fused=False) -> str:
    """Measure-once arbitration (TPU only; everywhere else the gathered
    XLA path is the portable winner and interpret-mode timing would be
    meaningless)."""
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS") or not on_tpu():
        return "xla"
    from . import autobench
    S, H, d = q.shape
    P, ps = k_pages.shape[-4], k_pages.shape[-3]
    M = page_table.shape[1]
    key, cands, make_args = _gate_paged(S, H, d, P, ps, M, q.dtype,
                                        Hkv=k_pages.shape[-2], fused=fused)
    return autobench.prefer(key, cands, make_args, default="xla")


def _warm_paged(spec: dict) -> str:
    from . import autobench
    key, cands, make_args = _gate_paged(
        int(spec["s"]), int(spec["h"]), int(spec["d"]), int(spec["p"]),
        int(spec["ps"]), int(spec["m"]), spec.get("dtype", "bfloat16"),
        Hkv=int(spec["hkv"]) if "hkv" in spec else None,
        fused=bool(spec.get("fused", False)))
    return autobench.prefer(key, cands, make_args, default="xla")


def _register_warmer():
    from . import autobench
    autobench.register_warmer("paged_attention", _warm_paged)


_register_warmer()


def paged_attention_decode(q, k_pages, v_pages, page_table, ctx_lens,
                           scale=None, impl=None, layer=None):
    """Ragged paged-attention decode; see module docstring for layouts.

    impl: None = auto (XLA everywhere; on TPU the Pallas kernel is
    auto-benchmarked per shape and used where it wins), or force
    "xla" / "pallas"."""
    if impl is None:
        impl = _auto_impl(q, k_pages, page_table, fused=v_pages is None)
    fn = paged_attention_pallas if impl == "pallas" else paged_attention_xla
    return fn(q, k_pages, v_pages, page_table, ctx_lens, scale, layer=layer)


@register("paged_attention", grad=None,
          infer_shape=same_shape_as("Q"),
          attrs={"scale": 0.0, "impl": ""},
          no_grad_slots=("PageTable", "CtxLens"))
def _paged_attention_op(ctx, ins, attrs):
    """Op form so deserialized/static serving programs can spell the
    decode step as a graph op (inference-only: grad=None)."""
    q = x(ins, "Q")
    o = paged_attention_decode(
        q, x(ins, "KCache"), x(ins, "VCache"), x(ins, "PageTable"),
        x(ins, "CtxLens"), scale=attrs.get("scale") or None,
        impl=attrs.get("impl") or None)
    return {"Out": [o]}

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

Layouts:
  q          [S, H, d]        one query token per slot
  k/v_pages  [P, ps, H, d]    the page pools of one layer, or stacked
             [L, P, ps, H, d]  over layers with
  layer      int32 scalar     which layer's pages to read (may be traced)
  page_table [S, M] int32     pool index of each slot's m-th page
  ctx_lens   [S] int32        valid history length per slot (>= 1)
Returns     [S, H, d]

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
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
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
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    k = k_pages[layer, page_table].reshape(S, M * ps, H, d)
    v = v_pages[layer, page_table].reshape(S, M * ps, H, d)
    logits = jnp.einsum("shd,sthd->sht", q, k,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(M * ps, dtype=jnp.int32)[None, :]
    logits = jnp.where(pos[:, None, :] < ctx_lens[:, None, None],
                       logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("sht,sthd->shd", probs.astype(v.dtype), v)
    return o.astype(q.dtype)


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


def paged_attention_pallas(q, k_pages, v_pages, page_table, ctx_lens,
                           scale=None, interpret=None, layer=None):
    S, H, d = q.shape
    k_pages, v_pages, layer = _stacked(k_pages, v_pages, layer)
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


def _gate_paged(S, H, d, P, ps, M, dtype):
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

    def make_args():
        import numpy as np
        rng = np.random.RandomState(0)
        qq = jnp.asarray(rng.randn(S, H, d), dtype)
        kk = jnp.asarray(rng.randn(1, P, ps, H, d), dtype)
        vv = jnp.asarray(rng.randn(1, P, ps, H, d), dtype)
        pt = jnp.asarray(rng.randint(0, P, (S, M)), jnp.int32)
        ln = jnp.asarray(rng.randint(1, M * ps + 1, (S,)), jnp.int32)
        return qq, kk, vv, pt, ln, jnp.zeros((), jnp.int32)

    def xla(qq, kk, vv, pt, ln, layer):
        return paged_attention_xla(qq, kk, vv, pt, ln, layer=layer)

    def pallas(qq, kk, vv, pt, ln, layer):
        return paged_attention_pallas(qq, kk, vv, pt, ln, interpret=False,
                                      layer=layer)

    return key, {"xla": xla, "pallas": pallas}, make_args


def _auto_impl(q, k_pages, page_table) -> str:
    """Measure-once arbitration (TPU only; everywhere else the gathered
    XLA path is the portable winner and interpret-mode timing would be
    meaningless)."""
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS") or not on_tpu():
        return "xla"
    from . import autobench
    S, H, d = q.shape
    P, ps = k_pages.shape[-4], k_pages.shape[-3]
    M = page_table.shape[1]
    key, cands, make_args = _gate_paged(S, H, d, P, ps, M, q.dtype)
    return autobench.prefer(key, cands, make_args, default="xla")


def _warm_paged(spec: dict) -> str:
    from . import autobench
    key, cands, make_args = _gate_paged(
        int(spec["s"]), int(spec["h"]), int(spec["d"]), int(spec["p"]),
        int(spec["ps"]), int(spec["m"]), spec.get("dtype", "bfloat16"))
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
        impl = _auto_impl(q, k_pages, page_table)
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

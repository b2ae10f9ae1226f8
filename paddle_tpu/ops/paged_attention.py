"""Ragged paged-attention decode (PAPERS.md: Ragged Paged Attention).

The serving tier stores each request's KV history in fixed-size pages of
a preallocated HBM pool; decode computes one new token per in-flight
request ("slot") against its own ragged-length history. Two
implementations behind one function:

  * gather-based XLA: k_pages[layer, page_table] gathers each slot's
    pages into a [S, M*ps] context, masked past ctx_len — one fused XLA
    computation, the portable default;
  * a Pallas TPU kernel: grid (slot,), one program a slot. The pools stay
    in HBM; the layer, the page table and the contexts are
    scalar-prefetched, and the program copies its slot's own
    cdiv(ctx, ps) pages itself, B pages a block, the next block's copies
    in flight while this block goes through an online softmax. Where query
    heads SHARE their keys (G > 1 heads a KV head, or a fused pool) a KV
    head's block is one matrix and both products go to the MXU, a block
    and a KV head at a time (`_grouped_kernel`); plain multi-head
    attention (G = 1) has one query row a head, nothing for the MXU, and
    does its per-head mat-vecs on the VPU, a page at a time
    (`_paged_kernel`). What a call costs follows the contexts, not the
    table's width; a table entry past the context is never read.

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
  first      [S] int32        optional, the XLA path only: the first live
             position per slot (a layer that attends to a window: positions
             first .. ctx - 1); rows below `first` are masked as rows from
             ctx on are
  ring       int              optional, with `first`: the table is a RING of
             `ring` entries, logical page j lies in entry j mod ring (a
             window layer keeps window / ps + 1 pages a slot, whatever the
             context; serving/model.py::WindowedDecodeModel)
Returns     [S, H, d]

A head size under the 128 lanes of a TPU register makes a poor minor
dimension: the device pads it or turns the pool round so that pages lie
across lanes (looked at with the chip's compiler, PR 26). Such a model
keeps ONE fused pool `[L, P, ps, Hkv, 2d]`, K in the first d lanes of a
head and V in the last d, and passes it as `k_pages` with `v_pages=None`:
one gather, or one page DMA, brings both.

On the chip the kernel's copies move whole 128-lane tiles, so there the
minor dimension of a pool it is given is a multiple of 128 (d = 128, or a
fused pool of heads of 64); Mosaic refuses another, the gate keeps the
refusal with its decision and the XLA path runs. The grouped kernel reads
a KV head out of a block fast only where that minor dimension IS 128, the
pool bfloat16 and Hkv even (`_head_rows`): every model served so far.

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
           "paged_attention_pallas", "paged_latent_attention_decode",
           "paged_latent_attention_xla", "paged_latent_attention_pallas",
           "latent_row_width"]

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


def _live_rows(page_table, ps, ctx_lens, first, ring):
    """[S, M ps] bool: which gathered rows a slot attends to when its live
    positions are first .. ctx - 1. Entry e of a ring holds the newest
    logical page that is congruent to e and not past the context's last;
    without a ring entry e is page e."""
    M = page_table.shape[1]
    e = jnp.arange(M, dtype=jnp.int32)[None, :]
    if ring is None:
        page = jnp.broadcast_to(e, page_table.shape)
    else:
        last = (ctx_lens[:, None] - 1) // ps
        page = jnp.where(e < ring, last - (last - e) % ring, -1)
    pos = page[:, :, None] * ps + jnp.arange(ps, dtype=jnp.int32)
    pos = pos.reshape(page_table.shape[0], M * ps)
    return (pos >= first[:, None]) & (pos < ctx_lens[:, None]) & (pos >= 0)


def paged_attention_xla(q, k_pages, v_pages, page_table, ctx_lens,
                        scale=None, layer=None, first=None, ring=None):
    """Gather-based reference path; fully fused by XLA. `first`, `ring`:
    a window (the module says how). This path is a window's only
    implementation: the kernel walking a ring's live pages lost to it by
    2.1x at the one shape that asked (PERF.md, PR 40)."""
    if ring is not None and (first is None
                             or not 0 < ring <= page_table.shape[1]):
        raise ValueError(f"a ring of {ring} entries in a table of "
                         f"{page_table.shape[1]}, first "
                         f"{'missing' if first is None else 'given'}: a "
                         f"ring is walked from a slot's first live position")
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
    if first is not None:   # a window: rows by their logical position
        live = _live_rows(page_table, ps, ctx_lens, first, ring)
        logits = jnp.einsum("skgd,stkd->skgt", q.reshape(S, Hkv, G, d), k,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(live[:, None, None, :], logits, _NEG)
        probs = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("skgt,stkd->skgd", probs.astype(v.dtype), v)
        return o.reshape(S, H, d).astype(q.dtype)
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
# Pallas kernel: grid (slot,). One program a slot walks that slot's own
# pages, so a call's work follows the contexts and not the table's width.
# The pools stay in HBM (memory_space=pl.ANY); page_table, ctx_lens and the
# layer are scalar-prefetched and steer the kernel's own page copies.
# ---------------------------------------------------------------------------

# VMEM for the page copies: two buffers (one filled by the DMA engine while
# the other is read) of one block of B pages of each pool. 2 MiB keeps a
# MiB of copies in flight, enough to cover HBM's latency at its bandwidth;
# the working set beside them is one page of K and of V in float32 (64 KiB
# each; multi-head) or one KV head of a block (the grouped kernel: 128 KiB
# of K_h, as much of V_h, twice that as the words they are read as) and
# the whole stays far under Mosaic's 16 MiB of scoped VMEM.
_PAGE_BUFFER_BYTES = 2 * 2 ** 20


def _block_pages(page_bytes: int, n_pools: int, M: int) -> int:
    """B, the pages of one block: what the buffers hold, at most the table.
    GPT-1.3B (64 KiB a page, K and V pools): 8; LFM2 (32 KiB, fused): 32."""
    return max(1, min(_PAGE_BUFFER_BYTES // (2 * n_pools * page_bytes), M))


def _softmax_update(carry, q, k, v, live=None):
    """One page into the running softmax of heads that each have their own
    keys (G = 1). carry (m [H, 1], l [H, 1], acc [H, d]), float32; q
    [H, d], scaled; k, v: [ps, H, d] float32; live: [ps, H, 1] bool for a
    page the context ends in, None for a whole one.

    One query token per head against one page is a batched mat-vec, done
    on the VPU with the head axis kept in place. (The MXU spelling,
    einsum("hd,phd->hp"), puts the batch dimension in the middle of the
    rhs and leaves the lhs no free dimension; Mosaic refuses it, and a
    head at a time it would be a product of ONE row. Heads that SHARE
    keys are a matrix against them: `_grouped_kernel`.) Scores stay
    [ps, H, 1] so that they broadcast over d without a relayout and
    reduce over the page axis into [H, 1]."""
    m_prev, l_prev, acc = carry
    if live is not None:
        v = jnp.where(live, v, 0.0)     # rows nobody wrote: 0 x NaN is NaN
    scores = jnp.sum(q[None] * k, axis=-1, keepdims=True)
    if live is not None:
        scores = jnp.where(live, scores, _NEG)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=0))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new[None])                         # [ps, H, 1]
    if live is not None:    # a page all dead: exp(_NEG - _NEG) = 1
        p = jnp.where(live, p, 0.0)
    return (m_new, alpha * l_prev + jnp.sum(p, axis=0),
            acc * alpha + jnp.sum(p * v, axis=0))


def _walk_blocks(pt_ref, len_ref, layer, pools, bufs, sem, first_ref,
                 page_size, block, on_block, carry):
    """The page walk both kernels share: this program's slot is
    cdiv(n, B) blocks of its n = cdiv(ctx, ps) live pages. The copies of
    block i + 1 (or, after the last, of the NEXT slot's first block: the
    grid runs in order on one core and scratch outlives a program) are
    started before block i is waited for and handed to `on_block(i, b, n,
    carry) -> carry` in buffer b, so the arithmetic and the start of a
    slot hide behind copies in flight. A table entry past n is never
    read, nor is the page it names. Returns (carry, n, blocks walked, the
    buffer of the first); the caller stores which buffer the next slot
    starts in (`first_ref`) when it is done with the last."""
    ps, B = page_size, block
    s, S = pl.program_id(0), pl.num_programs(0)

    def n_pages(slot):      # ctx >= 1 is the contract; 0 reads as 1, dead
        return jnp.maximum((len_ref[slot] + ps - 1) // ps, 1)

    def copies(slot, i, b, act):
        """start or wait for the copies of block i of `slot` into buffer
        b: one a live page and pool."""
        def page(j, _):
            at = pt_ref[slot, i * B + j]
            for p in range(len(pools)):
                act(pltpu.make_async_copy(pools[p].at[layer, at],
                                          bufs[p].at[b, j], sem.at[p, b]))
            return _
        jax.lax.fori_loop(0, jnp.minimum(B, n_pages(slot) - i * B), page, 0)

    def start(slot, i, b):
        copies(slot, i, b, lambda c: c.start())

    @pl.when(s == 0)
    def _first():
        first_ref[0] = 0
        start(0, 0, 0)

    n, b0 = n_pages(s), first_ref[0]
    n_blocks = (n + B - 1) // B

    def one_block(i, carry):
        b = (b0 + i) % 2

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start(s, i + 1, 1 - b)

        @pl.when(jnp.logical_and(i + 1 == n_blocks, s + 1 < S))
        def _next_slot():
            start(s + 1, 0, 1 - b)

        copies(s, i, b, lambda c: c.wait())
        return on_block(i, b, n, carry)

    carry = jax.lax.fori_loop(0, n_blocks, one_block, carry)
    return carry, n, n_blocks, b0


def _paged_kernel(pt_ref, len_ref, ly_ref, q_ref, k_pool, v_pool, o_ref,
                  k_buf, v_buf, sem, first_ref, *, page_size, scale, block):
    """Multi-head (G = 1): the K and V pools in HBM [L, P, ps, H, d], q
    and o blocks [1, H, d], a VMEM buffer [2, B, ps, H, d] a pool, DMA
    semaphores [2, 2] (one a pool and buffer) and, in SMEM, which buffer
    holds the slot's first block. The pages arrive through `_walk_blocks`
    and go one at a time through `_softmax_update` on the VPU."""
    ps, B = page_size, block
    s = pl.program_id(0)

    def page_of(b, j):
        return (k_buf[b, j].astype(jnp.float32),
                v_buf[b, j].astype(jnp.float32))

    q = q_ref[0].astype(jnp.float32) * scale
    H, d = q.shape

    def whole_pages(i, b, n, carry):
        # every page but the slot's last is whole: no mask
        return jax.lax.fori_loop(
            0, jnp.minimum(B, n - 1 - i * B),
            lambda j, c: _softmax_update(c, q, *page_of(b, j)), carry)

    zero = jnp.zeros((H, 1), jnp.float32)
    carry, n, n_blocks, b0 = _walk_blocks(
        pt_ref, len_ref, ly_ref[0], (k_pool, v_pool), (k_buf, v_buf), sem,
        first_ref, ps, B, whole_pages,
        (zero + _NEG, zero, jnp.zeros((H, d), jnp.float32)))
    # the page the context ends in, still in the last block's buffer
    last = n_blocks - 1
    idx = (n - 1) * ps + jax.lax.broadcasted_iota(jnp.int32, (ps, H, 1), 0)
    _, l, acc = _softmax_update(
        carry, q, *page_of((b0 + last) % 2, n - 1 - last * B),
        live=idx < len_ref[s])
    first_ref[0] = (b0 + n_blocks) % 2
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _head_rows(block, h, words):
    """KV head h of a block of pages, block [B, ps, Hkv, w] in VMEM, as one
    matrix [B ps, w]: the rows h, h + Hkv, ... of the block folded to rows.
    `words`: bfloat16 pages of 128 lanes and an even number of heads, on
    the chip. Two neighbouring rows of 16 bits share a 32-bit word of a
    lane, heads 2j and 2j + 1 of a token: the words j, j + Hkv / 2, ...
    are read with a stride and the head's half is moved to the top of its
    word, which is that number as a float32: the read hides behind the
    block's copies. Otherwise the head is indexed, which the interpreters
    run (they know no folded ref) and Mosaic takes at any shape, a
    register a token: the whole kernel 23.7 ms where the words give 2.0
    (Trinity-Mini's call; PERF.md, PR 41)."""
    B, ps, Hkv, w = block.shape
    if not words:
        return block[:, :, h, :].reshape(B * ps, w)
    rows = block.reshape(B * ps * Hkv, w).bitcast(jnp.uint32)[
        pl.ds(h // 2, B * ps, stride=Hkv // 2), :]
    bits = rows & jnp.uint32(0xFFFF0000) if h % 2 else rows << 16
    return pltpu.bitcast(bits, jnp.float32).astype(block.dtype)


def _softmax_block(carry, q, k, v, live, scale):
    """A block of T cached tokens into the running softmax of the query
    heads that share them. carry (m [H, 1], l [H, 1], acc [H, c]) float32;
    q [H, w], k [T, w], v [T, c] in the pool's dtype; live [1, T] bool.
    Both products on the MXU with float32 results, the probabilities
    rounded to the pool's dtype as the XLA path rounds them."""
    m_prev, l_prev, acc = carry
    # the MXU's own precision for the pool's dtype, whatever the
    # process's default (Mosaic refuses bf16 operands at `highest`)
    sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.DEFAULT,
                             preferred_element_type=jnp.float32) * scale
    sc = jnp.where(live, sc, _NEG)      # all true but in the last block
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(sc - m_new)     # a walked block has a live token: dead = 0
    pv = jnp.dot(p.astype(v.dtype), v,
                 precision=jax.lax.Precision.DEFAULT,
                 preferred_element_type=jnp.float32)
    return (m_new, alpha * l_prev + jnp.sum(p, axis=1, keepdims=True),
            acc * alpha + pv)


def _grouped_kernel(pt_ref, len_ref, ly_ref, q_ref, *refs, page_size, scale,
                    block, words):
    """G query heads a KV head, or a fused pool: refs are the pools in HBM
    ([L, P, ps, Hkv, w]: K and V, or one fused [K | V] pool), o_ref, a
    VMEM buffer [2, B, ps, Hkv, w] a pool, DMA semaphores [pools, 2] and
    the SMEM word of `_paged_kernel`; q and o blocks group-major [1, G,
    Hkv, w]. KV head h of a block of B pages is one matrix K_h [B ps, w]
    (`_head_rows`) that the head's G query heads all meet, so scores [G, B
    ps] = q_h . K_h^T and acc_h += p . V_h go to the MXU, a KV head at a
    time, the softmax state float32 a KV head. Fused pool: one read brings
    [K | V]; q has zeros in the V lanes and the wrapper keeps the
    accumulator's V lanes. Only a slot's last block has dead tokens,
    masked as `_latent_kernel` masks them."""
    ps, B = page_size, block
    n_pools = (len(refs) - 3) // 2
    pools, o_ref = refs[:n_pools], refs[n_pools]
    bufs, (sem, first_ref) = refs[n_pools + 1:-2], refs[-2:]
    _, G, Hkv, w = q_ref.shape
    T = B * ps
    ctx = len_ref[pl.program_id(0)]
    q = [q_ref[0, :, h, :] for h in range(Hkv)]

    def on_block(i, b, n, carry):
        last = (i + 1) * B >= n
        at = i * T
        live_col = at + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0) < ctx
        live = at + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1) < ctx
        out = []
        for h, state in enumerate(carry):
            # rows nobody wrote may hold anything, and 0 x NaN is NaN; a
            # dead key's score is masked, whatever it is
            v = jax.lax.cond(
                last, lambda r: jnp.where(live_col, r, jnp.zeros_like(r)),
                lambda r: r, _head_rows(bufs[-1].at[b], h, words))
            k = v if n_pools == 1 else _head_rows(bufs[0].at[b], h, words)
            out.append(_softmax_block(state, q[h], k, v, live, scale))
        return tuple(out)

    zero = jnp.zeros((G, 1), jnp.float32)
    carry, _n, n_blocks, b0 = _walk_blocks(
        pt_ref, len_ref, ly_ref[0], pools, bufs, sem, first_ref, ps, B,
        on_block,
        ((zero + _NEG, zero, jnp.zeros((G, w), jnp.float32)),) * Hkv)
    first_ref[0] = (b0 + n_blocks) % 2
    for h, (_, l, acc) in enumerate(carry):
        o_ref[0, :, h, :] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _paged_call(q, pools, page_table, ctx_lens, scale, interpret, layer):
    """The kernel over q [S, H, d] (G = 1) or group-major [S, G, Hkv, w];
    the output has q's shape."""
    ps, Hkv, w = pools[0].shape[2:]
    B = _block_pages(ps * Hkv * w * pools[0].dtype.itemsize, len(pools),
                     page_table.shape[1])
    heads = pl.BlockSpec((1,) + q.shape[1:],
                         lambda s, *_: (s,) + (0,) * (q.ndim - 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(q.shape[0],),
        in_specs=[heads] + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=heads,
        scratch_shapes=[pltpu.VMEM((2, B, ps, Hkv, w), p.dtype)
                        for p in pools]
        + [pltpu.SemaphoreType.DMA((len(pools), 2)),
           pltpu.SMEM((1,), jnp.int32)],
    )
    if q.ndim == 4:
        kernel = functools.partial(
            _grouped_kernel,
            words=(not interpret and w == LATENT_LANES and Hkv % 2 == 0
                   and pools[0].dtype == jnp.bfloat16))
    else:
        kernel = _paged_kernel
    kernel = functools.partial(kernel, page_size=ps, scale=float(scale),
                               block=B)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # in order on one core: a program starts its successor's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      layer.reshape(1), q, *pools)


def _paged_attention_pallas_gqa(q, pools, page_table, ctx_lens, scale,
                                interpret, layer):
    """G query heads a KV head, or a fused pool: q goes in group-major
    [S, G, Hkv, w] (zeros in a fused pool's V lanes) and the custom call's
    output is bf16[S, G, Hkv, w]."""
    S, H, d = q.shape
    Hkv, w = pools[0].shape[3:]
    G = H // Hkv
    q = q.reshape(S, Hkv, G, d).transpose(0, 2, 1, 3)
    if w != d:
        q = jnp.concatenate([q, jnp.zeros_like(q)], axis=-1)
    o = _paged_call(q, pools, page_table, ctx_lens, scale, interpret, layer)
    return o[..., w - d:].transpose(0, 2, 1, 3).reshape(S, H, d)


def paged_attention_pallas(q, k_pages, v_pages, page_table, ctx_lens,
                           scale=None, interpret=None, layer=None):
    d = q.shape[-1]
    k_pages, v_pages, layer = _stacked(k_pages, v_pages, layer)
    pools = (k_pages,) if v_pages is None else (k_pages, v_pages)
    call = _paged_attention_pallas_gqa \
        if _groups(q, k_pages) > 1 or v_pages is None \
        else _paged_call        # multi-head: the custom call is bf16[S, H, d]
    return call(q, pools, page_table, ctx_lens,
                scale if scale is not None else 1.0 / math.sqrt(d),
                (not on_tpu()) if interpret is None else interpret, layer)


# ---------------------------------------------------------------------------
# Latent rows (multi-head latent attention, absorbed form). A token's state
# in a layer is ONE row [c | kr] with no head axis: every query head reads
# it, as key over its whole width and as value over its first
# `value_width` lanes. H query heads against one row is a matrix product,
# so this kernel's arithmetic goes to the MXU, a block of pages at a time.
# ---------------------------------------------------------------------------

LATENT_LANES = 128      # a pool row is padded to whole registers' lanes


def latent_row_width(width: int) -> int:
    """Lanes of a pool row that holds `width` numbers a token: the next
    multiple of 128 (576 -> 640). The kernel's page copies move whole
    128-lane tiles, and the device pads another minor dimension to them
    anyway (scripts/latent_kernel_step0.py weighs the other layouts)."""
    return -(-width // LATENT_LANES) * LATENT_LANES


def paged_latent_attention_xla(q, rows, page_table, ctx_lens, value_width,
                               scale, layer):
    """Gather-based path. q [S, H, w], rows [L, P, ps, W >= w]."""
    S, H, w = q.shape
    M, ps = page_table.shape[1], rows.shape[2]
    kv = rows[layer, page_table].reshape(S, M * ps, rows.shape[3])
    logits = jnp.einsum("shw,stw->sht", q, kv[..., :w],
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(M * ps, dtype=jnp.int32)[None, None, :]
    logits = jnp.where(pos < ctx_lens[:, None, None], logits, _NEG)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("sht,stc->shc", probs.astype(kv.dtype),
                   kv[..., :value_width])
    return o.astype(q.dtype)


def _latent_kernel(pt_ref, len_ref, ly_ref, q_ref, rows_ref, o_ref, buf, sem,
                   first_ref, *, page_size, scale, block, value_width):
    """q block [1, H, W] (zeros past the query's own width), o block [1, H,
    value_width], buf [2, B, ps, W]. A block of B pages is one [B ps, W]
    matrix: scores [H, B ps] = q . rows^T and acc += p . rows[:, :value
    width], both on the MXU in the pool's dtype with float32 results.
    Only a slot's last block has dead tokens (the tail of its last page,
    and pages of the buffer it did not fill): there the scores are masked
    and the rows zeroed (a stale row may hold anything, and 0 x NaN is
    NaN); every other block runs unmasked."""
    ps, B = page_size, block
    s = pl.program_id(0)
    q = q_ref[0]
    H, W = q.shape
    T = B * ps
    ctx = len_ref[s]

    def on_block(i, b, n, carry):
        kv = buf[b].reshape(T, W)
        last = (i + 1) * B >= n
        at = i * T
        live_col = at + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0) < ctx
        kv = jax.lax.cond(last,
                          lambda r: jnp.where(live_col, r, jnp.zeros_like(r)),
                          lambda r: r, kv)
        live = at + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1) < ctx
        return _softmax_block(carry, q, kv, kv[:, :value_width], live, scale)

    zero = jnp.zeros((H, 1), jnp.float32)
    (_, l, acc), _n, n_blocks, b0 = _walk_blocks(
        pt_ref, len_ref, ly_ref[0], (rows_ref,), (buf,), sem, first_ref, ps,
        B, on_block,
        (zero + _NEG, zero, jnp.zeros((H, value_width), jnp.float32)))
    first_ref[0] = (b0 + n_blocks) % 2
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_latent_attention_pallas(q, rows, page_table, ctx_lens, value_width,
                                  scale, layer, interpret=None):
    """The kernel over q [S, H, w] and rows [L, P, ps, W]; the custom
    call's output is [S, H, value_width]."""
    S, H, w = q.shape
    ps, W = rows.shape[2:]
    if w < W:
        q = jnp.concatenate([q, jnp.zeros((S, H, W - w), q.dtype)], axis=-1)
    B = _block_pages(ps * W * rows.dtype.itemsize, 1, page_table.shape[1])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, H, W), lambda s, *_: (s, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, value_width), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, B, ps, W), rows.dtype),
                        pltpu.SemaphoreType.DMA((1, 2)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    kernel = functools.partial(_latent_kernel, page_size=ps,
                               scale=float(scale), block=B,
                               value_width=value_width)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=(not on_tpu()) if interpret is None else interpret,
    )(page_table.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q.astype(rows.dtype), rows)


def _gate_latent(S, H, w, value_width, P, ps, M, W, dtype):
    """(key, candidates, make_args) of the latent path's gate, as
    `_gate_paged`: a stack of one layer, the layer an argument."""
    dtype = jnp.dtype(dtype)
    key = ("paged_attention", "latent", S, H, w, value_width, P, ps, M, W,
           str(dtype))
    scale = 1.0 / math.sqrt(w)

    def make_args():
        import numpy as np
        rng = np.random.RandomState(0)
        kq, kr = jax.random.split(jax.random.PRNGKey(0))
        return (jax.random.normal(kq, (S, H, w), dtype),
                jax.random.normal(kr, (1, P, ps, W), dtype),   # on the device
                jnp.asarray(rng.randint(0, P, (S, M)), jnp.int32),
                jnp.asarray(rng.randint(1, M * ps + 1, (S,)), jnp.int32),
                jnp.zeros((), jnp.int32))

    def xla(qq, rr, pt, ln, layer):
        return paged_latent_attention_xla(qq, rr, pt, ln, value_width, scale,
                                          layer)

    def pallas(qq, rr, pt, ln, layer):
        return paged_latent_attention_pallas(qq, rr, pt, ln, value_width,
                                             scale, layer, interpret=False)

    return key, {"xla": xla, "pallas": pallas}, make_args


def paged_latent_attention_decode(q, rows, page_table, ctx_lens, *,
                                  value_width, scale, layer, impl=None):
    """Ragged paged attention over latent rows: q [S, H, w] (the absorbed
    query [q' | q_rope]) against the cached rows [L, P, ps, W] of `layer`
    (int32 scalar, may be traced; W = `latent_row_width(w)`, lanes past w
    are never written and stay zero), the value a row's first
    `value_width` lanes. Returns [S, H, value_width]. impl as
    `paged_attention_decode`'s."""
    if impl is None:
        impl = "xla"
        if not os.environ.get("PADDLE_TPU_DISABLE_PALLAS") and on_tpu():
            from . import autobench
            S, H, w = q.shape
            key, cands, make_args = _gate_latent(
                S, H, w, value_width, rows.shape[1], rows.shape[2],
                page_table.shape[1], rows.shape[3], q.dtype)
            impl = autobench.prefer(key, cands, make_args, default="xla")
    fn = paged_latent_attention_pallas if impl == "pallas" \
        else paged_latent_attention_xla
    return fn(q, rows, page_table, ctx_lens, value_width, scale, layer)


def _gate_paged(S, H, d, P, ps, M, dtype, Hkv=None, fused=False):
    """(key, candidates, make_args) — shared by the decode-path gate and
    the autobench warm CLI (a fleet replica shipping a pre-warmed cache
    skips first-request measurement on its decode hot path).

    The candidates are timed in the form that runs, a stacked pool with
    the layer an argument of the jitted call, on a stack of ONE layer:
    neither candidate's time depends on L, and a serving engine's own
    pool leaves no room for a second one beside it. "live_pages" in the
    key (after "stacked", PR 25) keeps a record measured on the kernel
    of before, whose time was the table's width whatever the contexts,
    from answering for this one: trees share a machine's gate cache.
    "mxu" does the same for the grouped kernel's keys (PR 41: before it
    the candidate named `pallas` was the VPU loop, 6 x slower at
    Trinity-Mini's shape)."""
    dtype = jnp.dtype(dtype)
    key = ("paged_attention", "live_pages", S, H, d, P, ps, M, str(dtype))
    Hkv = H if Hkv is None else Hkv
    if Hkv != H or fused:   # `_grouped_kernel`, a key of its own
        key += ("mxu", "kv_heads", Hkv) + (("fused",) if fused else ())

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

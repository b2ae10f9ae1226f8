"""Pallas TPU flash (blockwise) attention — fwd + bwd kernels.

TPU-native replacement for the reference's fused attention CUDA tier
(/root/reference/paddle/fluid/operators/fused/multihead_matmul_op.cc:1,
/root/reference/paddle/fluid/operators/math/bert_encoder_functor.cu:1).
Design: online-softmax blockwise attention (flash attention) so the S×T
score matrix never materialises in HBM — Q blocks stream over K/V blocks
held in VMEM, accumulating in f32 on the MXU. Backward recomputes P from
the saved logsumexp (no S×T residual), with split dQ and dK/dV kernels.

Dropout runs INSIDE the kernel via a counter-based hash (murmur3
finaliser) of each score's global (batch·head, row, col) id, so forward
and backward regenerate the identical keep mask without ever materialising
it — and independently of block-size choices.

Numerical contract: matches `sdpa_reference` (jnp) to bf16 tolerance;
exercised by tests/test_pallas_kernels.py in interpret mode on CPU and by
the training cells (benchmark/runners/train.py) on the chip.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "can_use_flash", "on_tpu"]

_NEG_INF = -1e30


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _interpret() -> bool:
    return not on_tpu()


def _auto_block(n, env_name):
    """Largest block in (512, 256, 128, 64) dividing n, overridable via
    the env var. Measured end-to-end on v5e (BERT-base seq-512 train
    step): (512,512) @ 26.8% MFU beats (128,512) @ 24.5% — an isolated
    attention microbench prefers 128 q-blocks, but inside the fused step
    the extra grid iterations lose."""
    env = os.environ.get(env_name)
    if env and n % int(env) == 0:
        return int(env)
    for b in (512, 256, 128, 64):
        if n % b == 0:
            return b
    return None


def _auto_block_q(n):
    return _auto_block(n, "PADDLE_TPU_FLASH_BLOCK_Q")


def _auto_block_k(n):
    return _auto_block(n, "PADDLE_TPU_FLASH_BLOCK_K")


def can_use_flash(q, k, v, mask, dropout_p=0.0, block_q=None,
                  block_k=None) -> bool:
    """Gate for the Pallas path: TPU (or interpret-mode tests), block-aligned
    sequence lengths, and a padding-style mask (B,1,1,T) or none."""
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS"):
        return False
    if not (on_tpu() or os.environ.get("PADDLE_TPU_PALLAS_INTERPRET")):
        return False
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        return False
    s, d = q.shape[2], q.shape[3]
    t = k.shape[2]
    block_q = block_q or _auto_block_q(s)
    block_k = block_k or _auto_block_k(t)
    if block_q is None or block_k is None:
        return False
    if s % block_q or t % block_k or d % 8 or d > 256:
        return False
    if mask is not None:
        # only padding-style masks: (B,1,1,T) matching q's batch and k's
        # length exactly (broadcastable variants fall back to sdpa)
        if (mask.ndim != 4 or mask.shape[1] != 1 or mask.shape[2] != 1 or
                mask.shape[0] != q.shape[0] or mask.shape[3] != t):
            return False
    return True


# ---------------------------------------------------------------------------
# kernels. Layouts: q/k/v/do (BH, S|T, D); lse/delta (BH, S, 128)
# lane-broadcast f32; mask (B, 8, T) sublane-broadcast additive; seed
# (1,) int32 in SMEM. The 128/8 broadcasts satisfy TPU min-tile rules
# (same trick as the stock jax flash kernel's l/m residuals).
# ---------------------------------------------------------------------------

def _keep_mask(seed_ref, bh, rows, cols, t, dropout_p):
    """Deterministic per-element keep mask: murmur3-finalise a counter
    built from the global element id. Works identically on TPU and in
    interpret mode (no pltpu.prng dependency), and identically between
    forward and backward whatever the block partitioning."""
    salt = (jnp.uint32(bh) * jnp.uint32(0x9e3779b9) +
            jnp.uint32(seed_ref[0]))
    x = salt ^ ((rows * t + cols).astype(jnp.uint32) *
                jnp.uint32(0x85ebca6b))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85ebca6b)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xc2b2ae35)
    x = x ^ (x >> 16)
    thr = jnp.uint32(min(int(dropout_p * 4294967296.0), 4294967295))
    return x >= thr


def _first_k_block(iq, bq, block_k, window):
    """The first K block a q block's rows can reach inside the band: row
    r attends to r - window + 1 .. r (0 without a band)."""
    if window is None:
        return 0
    return jax.lax.div(jnp.maximum(iq * bq - (window - 1), 0), block_k)


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *,
                scale, causal, block_k, dropout_p, window=None):
    bh, iq = pl.program_id(0), pl.program_id(1)
    q = q_ref[0]                                        # (Bq, D) native dtype
    bq, d = q.shape
    t = k_ref.shape[1]
    nk = t // block_k
    hi = jnp.minimum(jax.lax.div((iq + 1) * bq + block_k - 1, block_k), nk) \
        if causal else nk
    lo = _first_k_block(iq, bq, block_k, window)

    def body(j, carry):
        acc, m_i, l_i = carry
        kblk = k_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if mask_ref is not None:
            s = s + mask_ref[0, 0:1, pl.ds(j * block_k, block_k)] \
                .astype(jnp.float32)
        rows = iq * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 0)
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        if causal:
            s = jnp.where(rows >= cols, s, _NEG_INF)
        if window is not None:
            # a row whose first block lies wholly before its band reads
            # p = 1 there; the next block's alpha = exp(-1e30 - m) wipes it
            s = jnp.where(rows - cols < window, s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=-1)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref, bh, rows, cols, t, dropout_p)
            p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        vblk = v_ref[0, pl.ds(j * block_k, block_k), :]
        acc = acc * alpha[:, None] + jnp.dot(
            p.astype(vblk.dtype), vblk, preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc, m_i, l_i = jax.lax.fori_loop(
        lo, hi, body, (jnp.zeros((bq, v_ref.shape[2]), jnp.float32),
                      jnp.full((bq,), _NEG_INF, jnp.float32),
                      jnp.zeros((bq,), jnp.float32)))
    l_safe = jnp.where(l_i == 0.0, 1.0, l_i)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # lane-broadcast to 128 (TPU min tile; same layout as the stock jax
    # flash kernel's l/m residuals)
    lse_ref[0] = jax.lax.broadcast_in_dim(
        m_i + jnp.log(l_safe), (bq, 128), (0,))


def _recompute_p(q, kblk, scale, mask_blk, lse_col, causal, rows, cols,
                 window=None):
    s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if mask_blk is not None:
        s = s + mask_blk
    if causal:
        s = jnp.where(rows >= cols, s, _NEG_INF)
    if window is not None:
        s = jnp.where(rows - cols < window, s, _NEG_INF)
    return jnp.exp(s - lse_col)


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   mask_ref, dq_ref, *, scale, causal, block_k, dropout_p,
                   window=None):
    bh, iq = pl.program_id(0), pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse_col = lse_ref[0][:, 0:1]
    delta_col = delta_ref[0][:, 0:1]
    bq, d = q.shape
    t = k_ref.shape[1]
    nk = t // block_k
    hi = jnp.minimum(jax.lax.div((iq + 1) * bq + block_k - 1, block_k), nk) \
        if causal else nk

    def body(j, dq):
        kblk = k_ref[0, pl.ds(j * block_k, block_k), :]
        vblk = v_ref[0, pl.ds(j * block_k, block_k), :]
        mask_blk = None
        if mask_ref is not None:
            mask_blk = mask_ref[0, 0:1, pl.ds(j * block_k, block_k)] \
                .astype(jnp.float32)
        rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        p = _recompute_p(q, kblk, scale, mask_blk, lse_col, causal, rows,
                         cols, window)
        dp = jax.lax.dot_general(do, vblk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref, bh, rows, cols, t, dropout_p)
            dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
        ds = (p * (dp - delta_col) * scale).astype(kblk.dtype)
        return dq + jnp.dot(ds, kblk, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(_first_k_block(iq, bq, block_k, window), hi, body,
                           jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    mask_ref, dk_ref, dv_ref, *, scale, causal, block_q,
                    dropout_p):
    bh, jk = pl.program_id(0), pl.program_id(1)
    nk = pl.num_programs(1)
    kblk = k_ref[0]                                     # (Bk, D) native
    vblk = v_ref[0]
    bk, d = kblk.shape
    s_len = q_ref.shape[1]
    s_len_t = nk * bk  # kv length (hash uses row*T+col global ids)
    nq = s_len // block_q
    mask_blk = mask_ref[0, 0:1, :].astype(jnp.float32) \
        if mask_ref is not None else None
    lo = jax.lax.div(jk * bk, block_q) if causal else 0

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse_col = lse_ref[0, pl.ds(i * block_q, block_q), 0:1]
        delta_col = delta_ref[0, pl.ds(i * block_q, block_q), 0:1]
        rows = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 0)
        cols = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
        p = _recompute_p(q, kblk, scale, mask_blk, lse_col, causal, rows,
                         cols)
        dp = jax.lax.dot_general(do, vblk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref, bh, rows, cols, s_len_t, dropout_p)
            pd = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
            dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
        else:
            pd = p
        dv = dv + jax.lax.dot_general(pd.astype(do.dtype), do,
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_col) * scale).astype(q.dtype)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    z = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, nq, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_dkv_span_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, mask_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                         *, scale, causal, nq, span, window, group,
                         dropout_p):
    """dk/dv of one K block from the q blocks that can reach it, one q
    block of one query head a grid step: grid (B Hkv, K blocks, group,
    span). The whole-sequence spelling above keeps every q row of a head
    in VMEM, which a long sequence does not afford eight heads over; here
    a K block's accumulators stay in scratch across its group's heads and
    its span of q blocks (the band's few, or every later block), and a
    step past the last reachable block does nothing (its index map names
    the block before it again, so nothing is fetched)."""
    bkv, jk = pl.program_id(0), pl.program_id(1)
    g, r = pl.program_id(2), pl.program_id(3)
    kblk, vblk = k_ref[0], v_ref[0]
    bk, d = kblk.shape
    block_q = q_ref.shape[1]
    i = _first_q_block(jk, bk, block_q, causal) + r
    last = _last_q_block(jk, bk, block_q, nq, window)

    @pl.when(jnp.logical_and(g == 0, r == 0))
    def _zero():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(i <= last)
    def _add():
        q, do = q_ref[0], do_ref[0]
        rows = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 0)
        cols = jk * bk + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 1)
        mask_blk = mask_ref[0, 0:1, :].astype(jnp.float32) \
            if mask_ref is not None else None
        p = _recompute_p(q, kblk, scale, mask_blk, lse_ref[0][:, 0:1],
                         causal, rows, cols, window)
        dp = jax.lax.dot_general(do, vblk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref, bkv * group + g, rows, cols,
                              pl.num_programs(1) * bk, dropout_p)
            pd = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
            dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
        else:
            pd = p
        dv_acc[...] += jax.lax.dot_general(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0][:, 0:1]) * scale).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(g == group - 1, r == span - 1))
    def _store():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _first_q_block(jk, bk, block_q, causal):
    return jax.lax.div(jk * bk, block_q) if causal else 0


def _last_q_block(jk, bk, block_q, nq, window):
    """The last q block with a row that reaches K block jk: its last
    column jk bk + bk - 1 is seen up to row jk bk + bk + window - 2."""
    if window is None:
        return nq - 1
    return jnp.minimum(
        jax.lax.div(jk * bk + bk + window - 2, block_q), nq - 1)


def _q_span(nq, bk, block_q, window):
    """Grid steps a K block takes over q blocks: all of them, or the most
    a band can touch."""
    if window is None:
        return nq
    return min(nq, (bk + window - 2) // block_q + 2)


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------

def _smem_seed_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


# what a long sequence's whole K and V (fwd, dq) may take of VMEM
_SPAN_VMEM_BYTES = 64 * 2 ** 20


def _kv_index(group):
    """Index map of the whole K or V of the KV head that query-head row b
    belongs to (the plain call's own row: its map stays the text it
    was)."""
    if group == 1:
        return lambda b, i: (b, 0, 0)
    return lambda b, i: (b // group, 0, 0)


def _named(kind, window, group):
    """pallas_call arguments of the banded and grouped calls: a name a
    trace tells the band's calls from the full layer's by (the profiler
    shows it as the instruction's), and room for the whole K and V of a
    long sequence. The plain call (no band, one query head a KV head)
    stays the program it was."""
    if window is None and group == 1:
        return {}
    return {"name": f"flash_{'band' if window is not None else 'full'}"
                    f"_{kind}",
            "compiler_params": pltpu.CompilerParams(
                vmem_limit_bytes=_SPAN_VMEM_BYTES)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q3, k3, v3, mask2, seed_arr, scale, causal, block_q, block_k,
           dropout_p, window=None, group=1):
    o, _ = _flash_fwd_impl(q3, k3, v3, mask2, seed_arr, scale, causal,
                           block_q, block_k, dropout_p, window, group)
    return o


def _flash_fwd_impl(q3, k3, v3, mask2, seed_arr, scale, causal, block_q,
                    block_k, dropout_p, window=None, group=1):
    """q3: (B Hq, S, D); k3, v3: (B Hkv, T, D) with Hq = group Hkv: the
    index map hands a KV head's rows to each of its `group` query heads,
    so K and V are never repeated in HBM. mask2: (B, 8, T) additive or
    None. v3 may be (.., T, Dv) with another width than q's and k's
    (latent attention's expanded form: 192-wide products, 128-wide
    values): the forward only, the backward kernels take one width."""
    bh, s, d = q3.shape
    t, dv = k3.shape[1], v3.shape[2]
    heads = bh // mask2.shape[0] if mask2 is not None else 1
    kv_at = _kv_index(group)
    in_specs = [
        _smem_seed_spec(),
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, t, d), kv_at),
        pl.BlockSpec((1, t, dv), kv_at),
    ]
    args = [seed_arr, q3, k3, v3]
    if mask2 is not None:
        in_specs.append(
            pl.BlockSpec((1, 8, t), lambda b, i: (b // heads, 0, 0)))
        args.append(mask2)

        def kfn(seed_ref, q_ref, k_ref, v_ref, m_ref, o_ref, lse_ref):
            _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, m_ref, o_ref, lse_ref,
                        scale=scale, causal=causal, block_k=block_k,
                        dropout_p=dropout_p, window=window)
    else:
        def kfn(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref):
            _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, None, o_ref, lse_ref,
                        scale=scale, causal=causal, block_k=block_k,
                        dropout_p=dropout_p, window=window)

    o, lse = pl.pallas_call(
        kfn, grid=(bh, s // block_q), in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), q3.dtype),
            jax.ShapeDtypeStruct((bh, s, 128), jnp.float32),
        ],
        interpret=_interpret(), **_named("fwd", window, group))(*args)
    return o, lse


def _flash_fwd(q3, k3, v3, mask2, seed_arr, scale, causal, block_q,
               block_k, dropout_p, window=None, group=1):
    o, lse = _flash_fwd_impl(q3, k3, v3, mask2, seed_arr, scale, causal,
                             block_q, block_k, dropout_p, window, group)
    return o, (q3, k3, v3, mask2, seed_arr, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, dropout_p, window, group,
               res, g):
    q3, k3, v3, mask2, seed_arr, o, lse = res
    bh, s, d = q3.shape
    t = k3.shape[1]
    heads = bh // mask2.shape[0] if mask2 is not None else 1
    delta = jnp.broadcast_to(
        jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                axis=-1, keepdims=True), (bh, s, 128))

    dq_in = [
        _smem_seed_spec(),
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # q
        pl.BlockSpec((1, t, d), _kv_index(group)),               # k
        pl.BlockSpec((1, t, d), _kv_index(group)),               # v
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # do
        pl.BlockSpec((1, block_q, 128), lambda b, i: (b, i, 0)),  # lse
        pl.BlockSpec((1, block_q, 128), lambda b, i: (b, i, 0)),  # delta
    ]
    dq_args = [seed_arr, q3, k3, v3, g, lse, delta]
    if mask2 is not None:
        dq_in.append(
            pl.BlockSpec((1, 8, t), lambda b, i: (b // heads, 0, 0)))
        dq_args.append(mask2)

        def dq_kfn(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   m_ref, dq_ref):
            _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                           delta_ref, m_ref, dq_ref, scale=scale,
                           causal=causal, block_k=block_k,
                           dropout_p=dropout_p, window=window)
    else:
        def dq_kfn(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref):
            _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                           delta_ref, None, dq_ref, scale=scale,
                           causal=causal, block_k=block_k,
                           dropout_p=dropout_p, window=window)

    dq = pl.pallas_call(
        dq_kfn, grid=(bh, s // block_q), in_specs=dq_in,
        out_specs=[pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q3.dtype)],
        interpret=_interpret(), **_named("dq", window, group))(*dq_args)[0]

    if window is not None or group > 1:
        dk, dv = _dkv_span(q3, k3, v3, mask2, seed_arr, g, lse, delta, scale,
                           causal, block_q, block_k, dropout_p, window, group)
        return dq, dk, dv, None, None

    kv_in = [
        _smem_seed_spec(),
        pl.BlockSpec((1, s, d), lambda b, j: (b, 0, 0)),         # q full
        pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),   # k block
        pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),   # v block
        pl.BlockSpec((1, s, d), lambda b, j: (b, 0, 0)),         # do full
        pl.BlockSpec((1, s, 128), lambda b, j: (b, 0, 0)),       # lse
        pl.BlockSpec((1, s, 128), lambda b, j: (b, 0, 0)),       # delta
    ]
    kv_args = [seed_arr, q3, k3, v3, g, lse, delta]
    if mask2 is not None:
        kv_in.append(
            pl.BlockSpec((1, 8, block_k), lambda b, j: (b // heads, 0, j)))
        kv_args.append(mask2)

        def dkv_kfn(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    m_ref, dk_ref, dv_ref):
            _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                            delta_ref, m_ref, dk_ref, dv_ref, scale=scale,
                            causal=causal, block_q=block_q,
                            dropout_p=dropout_p)
    else:
        def dkv_kfn(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref):
            _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                            delta_ref, None, dk_ref, dv_ref, scale=scale,
                            causal=causal, block_q=block_q,
                            dropout_p=dropout_p)

    dk, dv = pl.pallas_call(
        dkv_kfn, grid=(bh, t // block_k), in_specs=kv_in,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v3.dtype),
        ],
        interpret=_interpret())(*kv_args)
    return dq, dk, dv, None, None


def _dkv_span(q3, k3, v3, mask2, seed_arr, g, lse, delta, scale, causal,
              block_q, block_k, dropout_p, window, group):
    """The banded / grouped dk, dv call (`_bwd_dkv_span_kernel`)."""
    s, d = q3.shape[1:]
    bkv, t, _ = k3.shape
    nq = s // block_q
    span = _q_span(nq, block_k, block_q, window)
    kv_heads = bkv // mask2.shape[0] if mask2 is not None else 1

    def q_at(b, j, h, r):
        i = _first_q_block(j, block_k, block_q, causal) + r
        return (b * group + h, jnp.minimum(
            i, _last_q_block(j, block_k, block_q, nq, window)), 0)

    kv_at = lambda b, j, h, r: (b, j, 0)
    in_specs = [
        _smem_seed_spec(),
        pl.BlockSpec((1, block_q, d), q_at),                     # q
        pl.BlockSpec((1, block_k, d), kv_at),                    # k
        pl.BlockSpec((1, block_k, d), kv_at),                    # v
        pl.BlockSpec((1, block_q, d), q_at),                     # do
        pl.BlockSpec((1, block_q, 128), q_at),                   # lse
        pl.BlockSpec((1, block_q, 128), q_at),                   # delta
    ]
    args = [seed_arr, q3, k3, v3, g, lse, delta]
    kw = dict(scale=scale, causal=causal, nq=nq, span=span, window=window,
              group=group, dropout_p=dropout_p)
    if mask2 is not None:
        in_specs.append(pl.BlockSpec(
            (1, 8, block_k), lambda b, j, h, r: (b // kv_heads, 0, j)))
        args.append(mask2)

        def kfn(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                m_ref, dk_ref, dv_ref, dk_acc, dv_acc):
            _bwd_dkv_span_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref,
                                 lse_ref, delta_ref, m_ref, dk_ref, dv_ref,
                                 dk_acc, dv_acc, **kw)
    else:
        def kfn(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc):
            _bwd_dkv_span_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref,
                                 lse_ref, delta_ref, None, dk_ref, dv_ref,
                                 dk_acc, dv_acc, **kw)

    return pl.pallas_call(
        kfn, grid=(bkv, t // block_k, group, span), in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, block_k, d), kv_at),
                   pl.BlockSpec((1, block_k, d), kv_at)],
        out_shape=[jax.ShapeDtypeStruct((bkv, t, d), k3.dtype),
                   jax.ShapeDtypeStruct((bkv, t, d), v3.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        name=f"flash_{'band' if window is not None else 'full'}_dkv",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=_interpret())(*args)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, mask=None, scale=None, causal=False,
                    dropout_p=0.0, dropout_seed=0, block_q=None,
                    block_k=None, window=None):
    """q: (B,H,S,D); k, v: (B,Hkv,T,D) with H a whole multiple of Hkv (KV
    head j serves query heads G j .. G j + G - 1); mask: additive
    (B,1,1,T) or None. Returns (B,H,S,D).

    window: with `causal`, position i attends to j <= i with i - j <
    window (a band). The three kernels then start and stop at the band's
    blocks: work O(S window).

    The Pallas path; call `can_use_flash` first. On non-TPU hosts the same
    kernels run in interpreter mode (slow — tests only).
    """
    b, h, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"{h} query heads are not whole groups of {hkv} "
                         f"KV heads")
    if window is not None and (not causal or window < 1 or s != t):
        raise ValueError("a band needs causal self-attention and a window "
                         "of one position or more")
    block_q = block_q or _auto_block_q(s)
    block_k = block_k or _auto_block_k(t)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    q3 = q.reshape(b * h, s, d)
    k3 = k.reshape(b * hkv, t, d)
    v3 = v.reshape(b * hkv, t, v.shape[3])
    mask2 = None
    if mask is not None:
        mask2 = jnp.broadcast_to(mask.reshape(b, 1, t), (b, 8, t))
    seed_arr = jnp.asarray(dropout_seed, jnp.int32).reshape(1)
    o = _flash(q3, k3, v3, mask2, seed_arr, float(scale), bool(causal),
               int(block_q), int(block_k), float(dropout_p),
               None if window is None else int(window), h // hkv)
    return o.reshape(b, h, s, v.shape[3])

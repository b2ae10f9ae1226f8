"""Pallas TPU flash (blockwise) attention — fwd + bwd kernels.

TPU-native replacement for the reference's fused attention CUDA tier
(/root/reference/paddle/fluid/operators/fused/multihead_matmul_op.cc:1,
/root/reference/paddle/fluid/operators/math/bert_encoder_functor.cu:1).
Design: online-softmax blockwise attention (flash attention) so the S×T
score matrix never materialises in HBM — Q blocks stream over K/V blocks
held in VMEM, accumulating in f32 on the MXU. Backward recomputes P from
the saved logsumexp (no S×T residual), with split dQ and dK/dV kernels.
Under a causal mask (and a band inside it) the tile schedule follows the
mask: a tile it leaves nothing of is never multiplied, a tile its edge
crosses is taken out of the loop, every other tile pays no mask
arithmetic, and a sequence of 1,024 positions or fewer is one grid step a
head whose loops Python unrolls (`_walk`, `flash_schedule`;
docs/KERNELS.md, "The flash kernel's tile schedule").

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
    the env var. Long products are where a tile is cheap: measured end to
    end on v5e on a NON-causal encoder, where no tile is ever masked
    (BERT-base seq-512 train step: (512,512) @ 26.8% MFU beats (128,512)
    @ 24.5%). A masked call keeps them (each of the chip's four MXUs
    takes one 128-wide column tile of a product, so 512 columns are what
    keeps all four busy) and deals with its mask's edges inside the
    kernel (`_walk` below)."""
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


# ---------------------------------------------------------------------------
# the tile schedule. The mask lets row r see column c where
# c <= r < c + window (the triangle, and the band inside it). A tile it
# leaves nothing of is never multiplied, and one its edge does not cross
# pays no mask arithmetic. `_walk` is the one statement of it for the
# kernels that loop over tiles: they fold their tile bodies over it with
# traced block indices (or, for a short sequence, Python's), and
# `flash_schedule` folds a recorder over it with Python ints. The dk/dv
# span kernel, one tile a grid step, takes its bodies from `_span_bodies`,
# and so does the recorder.
# ---------------------------------------------------------------------------

def _div(a, b):
    """Floor division of a non-negative a, a Python int or traced."""
    return a // b if isinstance(a, int) else jax.lax.div(a, b)


def _mx(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.maximum(a, b)


def _mn(a, b):
    return min(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.minimum(a, b)


def _tiles(x0, nx, block, n, causal, window, flip):
    """(lo, mid_lo, mid_hi, hi), lo <= mid_lo <= mid_hi <= hi, over the n
    tiles of `block` positions on the other axis, for own positions x0 ..
    x0 + nx - 1 (q rows against K tiles; `flip`: K columns against q
    tiles): the mask leaves something of tiles [lo, hi) and all of
    [mid_lo, mid_hi). Python ints in, ints out; a bound that does not
    depend on x0 stays an int when x0 is traced."""
    if not causal:
        return 0, 0, n, n
    x1 = x0 + nx - 1
    if window is None and flip:         # rows c .. : from the diagonal on
        return (_mn(_div(x0, block), n),
                _mn(_div(x1 + block - 1, block), n), n, n)
    if window is None:                  # columns .. r: up to the diagonal
        hi = _mn(_div(x1, block) + 1, n)
        return 0, 0, _mn(_div(x0 + 1, block), hi), hi
    if flip:    # rows c .. c + window - 1 see column c
        first, last, full_first, full_last = \
            x0, x1 + window - 1, x1, x0 + window - 1
    else:       # row r sees columns r - window + 1 .. r
        first, last, full_first, full_last = \
            x0 - window + 1, x1, x1 - window + 1, x0
    lo = _mn(_div(_mx(first, 0), block), n)
    hi = _mn(_div(last, block) + 1, n)
    mid_lo = _mn(_mx(_div(_mx(full_first, 0) + block - 1, block), lo), hi)
    mid_hi = _mn(_mx(_div(full_last + 1, block), mid_lo), hi)
    return lo, mid_lo, mid_hi, hi


@functools.lru_cache(maxsize=None)
def _square_plan(b, n, window, flip):
    """Square tiles of b, an own block against the other axis' tile k
    tiles further on (k < 0: before it): what the mask leaves of it
    depends on k alone. Returns ((k_lo, k_hi) or None, edges): the ks
    whose tile no edge crosses, and those an edge does cross."""
    lo, mid_lo, mid_hi, hi = _tiles(n * b, b, b, 2 * n, True, window, flip)
    edges = tuple(j - n for j in range(lo, hi) if not mid_lo <= j < mid_hi
                  and -n < j - n < n)
    full = (max(mid_lo - n, 1 - n), min(mid_hi - n, n))
    return (full if full[0] < full[1] else None), edges


# a band's tiles that no edge crosses are spelt out one by one, not looped
# over, where they are this many at most
_SPELT = 2


def _py_loop(lo, hi, body, carry):
    for j in range(lo, hi):
        carry = body(j, carry)
    return carry


def _walk(i, own, other, n_other, causal, window, flip, carry, tile):
    """Folds `tile(carry, j, masked, live)` over the tiles j the mask
    leaves something of, of own block i (q rows; `flip`: K columns)
    against the other axis' n_other tiles of `other`, in the order of that
    axis: `masked` where the tile pays the mask's arithmetic, `live` None
    or a traced flag that is false where the tile lies outside the axis
    and all of it is to be masked. A call without a mask, or whose tiles
    are not square, is one loop over whole tiles, all of them masked
    where causal, as the kernels always had; under square tiles the tiles
    no edge crosses are one loop without the mask's arithmetic and each
    edge tile is straight-line code outside it. i a Python int: the loops
    are Python's."""
    dry = isinstance(i, int)
    loop = _py_loop if dry else jax.lax.fori_loop
    if not causal or own != other:
        lo, _, _, hi = _tiles(i * own, own, other, n_other, causal, window,
                              flip)
        return loop(lo, hi, lambda j, c: tile(c, j, causal, None), carry)

    full, edges = _square_plan(own, n_other, window, flip)

    def visit(k, masked, carry):
        """Tile i + k of the other axis. Where the block index is traced
        and the tile may lie outside the axis, it is visited all the same,
        at the axis' end and with `live` false, which masks all of it:
        straight-line code, where a `cond` would spill the carry around
        itself as a loop does."""
        j, live = i + k, None
        if k and dry:
            if not 0 <= j < n_other:
                return carry
        elif k:
            live = j >= 0 if k < 0 else j < n_other
            j = jnp.clip(j, 0, n_other - 1)
        return tile(carry, j, masked, live)

    for k in (k for k in edges if full is None or k < full[0]):
        carry = visit(k, True, carry)
    if full is None:
        return carry
    if full[1] - full[0] <= _SPELT:
        for k in range(*full):
            carry = visit(k, False, carry)
    else:
        carry = loop(_mx(i + full[0], 0), _mn(i + full[1], n_other),
                     lambda j, c: tile(c, j, False, None), carry)
    for k in (k for k in edges if k >= full[1]):
        carry = visit(k, True, carry)
    return carry


def _span_bodies(i, bounds, causal, window):
    """The dk/dv span kernel's step at q tile i of a K block whose
    `_tiles` are `bounds`: (when, masked) a body, `when` (a Python bool
    of ints, else traced) whether the step runs it. Without a band one
    body, masked whole where causal: a second, unmasked, for the tiles
    past the diagonal reads 19.8 ms where this reads 18.4 at Mellum's
    full layer (chip, PR 48). Under a band the tiles no edge crosses go
    unmasked."""
    _, mid_lo, mid_hi, hi = bounds
    if not causal or window is None:
        return [(i < hi, causal)]
    return [((i >= mid_lo) & (i < mid_hi), False),
            ((i < hi) & ((i < mid_lo) | (i >= mid_hi)), True)]


def _plain(window, group):
    """No band and one query head a KV head: the call whose dk/dv is the
    whole-sequence kernel, and which carries no name."""
    return window is None and group == 1


def flash_schedule(s, t, block_q, block_k, causal, window=None, call="fwd",
                   group=1):
    """What `call` (fwd, dq or dkv) multiplies, as a list of (r0, nr, c0,
    nc, masked): rows r0 .. r0 + nr by columns c0 .. c0 + nc of the
    scores, `masked` where the kernel applies the mask's arithmetic to
    them. The forward and dq calls walk a q block over K, the dk/dv call a
    K block over q: by `_walk`, or for a band or `group` query heads a KV
    head a tile a grid step of the span kernel."""
    nq, nk = s // block_q, t // block_k
    out = []
    if call == "dkv" and not _plain(window, group):
        for j in range(nk):
            bounds = _tiles(j * block_k, block_k, block_q, nq, causal,
                            window, True)
            for r in range(_q_span(nq, nk, block_k, block_q, causal,
                                   window)):
                i = bounds[0] + r
                out += [(i * block_q, block_q, j * block_k, block_k, masked)
                        for when, masked in _span_bodies(i, bounds, causal,
                                                         window) if when]
        return out
    dkv = call == "dkv"
    own, other, n_own, n_other = (block_k, block_q, nk, nq) if dkv else \
        (block_q, block_k, nq, nk)
    for i in range(n_own):
        def tile(carry, j, masked, live):
            x = (i * own, own, j * other, other)
            out.append((x[2:] + x[:2] if dkv else x) + (masked,))
        _walk(i, own, other, n_other, causal, window, dkv, None, tile)
    return out


def _ids(row0, col0, shape):
    return (row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _cut(s, row0, col0, window, masked, live=None):
    """Scores s (first row row0, first column col0) under the causal mask,
    and the band's, where the tile is `masked`: the rows' iota against the
    columns' plus the tile's offset, which is added on the scalar side.
    All of s masked where `live` (a traced flag) is false."""
    ok = live
    if masked:
        rows, cols = _ids(0, col0 - row0, s.shape)
        ok = rows >= cols
        if window is not None:
            ok = jnp.logical_and(ok, rows < cols + window)
        if live is not None:
            ok = jnp.logical_and(ok, live)
    return s if ok is None else jnp.where(ok, s, _NEG_INF)


def _at(j, block):
    """First position of tile j: Python's own, or traced and aligned."""
    return j * block if isinstance(j, int) else pl.multiple_of(j * block,
                                                               block)


def _fold(scale):
    """The scale goes onto q where that is exact, a power of two (1/8 at
    a head of 64): the same numbers. Else it stays on the float32 scores
    (a head of 128)."""
    return math.frexp(scale)[0] == 0.5


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


# a sequence this short is one grid step a head: its q blocks (the dk/dv
# call's K blocks) are walked one after the other with Python's block
# indices, so every loop of the walk is unrolled and no carry crosses a
# loop (a `fori_loop` over 512 x 512 tiles spills its 512-row carry around
# every trip, some 1,000 bundles a grid step where a tile is 1,400)
_ONE_STEP = 1024


def _per_step(n, block, causal):
    """Blocks of `block` a grid step holds: all of a short masked
    sequence's, else one."""
    return n // block if causal and n <= _ONE_STEP else 1


def _block_index(a, per, steps):
    """Index of block a of a grid step's `per`: Python's own where the
    grid has one step a head."""
    return a if steps == 1 else pl.program_id(1) * per + a


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *,
                scale, causal, block_q, block_k, steps, dropout_p,
                window=None):
    bh = pl.program_id(0)
    bq, t = block_q, k_ref.shape[1]
    per = q_ref.shape[1] // bq
    fold = causal and _fold(scale)
    s_scale = 1.0 if fold else scale

    def block(a):       # q block a of the grid step's
        iq = _block_index(a, per, steps)
        q = q_ref[0, a * bq:(a + 1) * bq, :]            # (Bq, D) native dtype
        if fold:
            q = q * scale

        def tile(carry, j, masked, live):
            acc, m_i, l_i = carry
            c0 = _at(j, block_k)
            kblk = k_ref[0, pl.ds(c0, block_k), :]
            s = jax.lax.dot_general(q, kblk, _NT,
                                    preferred_element_type=jnp.float32)
            if s_scale != 1.0:
                s = s * s_scale
            if mask_ref is not None:
                s = s + mask_ref[0, 0:1, pl.ds(c0, block_k)] \
                    .astype(jnp.float32)
            # a row whose first tile lies wholly before its band reads p = 1
            # there; the next tile's alpha = exp(-1e30 - m) wipes it
            s = _cut(s, iq * bq, c0, window, masked, live)
            m_new = jnp.maximum(m_i, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_i - m_new)
            l_new = alpha * l_i + jnp.sum(p, axis=-1, keepdims=True)
            if dropout_p > 0.0:
                keep = _keep_mask(seed_ref, bh, *_ids(iq * bq, c0, s.shape),
                                  t, dropout_p)
                p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
            vblk = v_ref[0, pl.ds(c0, block_k), :]
            acc = acc * alpha + jnp.dot(
                p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return acc, m_new, l_new

        acc, m_i, l_i = _walk(
            iq, bq, block_k, t // block_k, causal, window, False,
            (jnp.zeros((bq, v_ref.shape[2]), jnp.float32),
             jnp.full((bq, 1), _NEG_INF, jnp.float32),
             jnp.zeros((bq, 1), jnp.float32)), tile)
        l_safe = jnp.where(l_i == 0.0, 1.0, l_i)
        o_ref[0, a * bq:(a + 1) * bq, :] = (acc / l_safe).astype(o_ref.dtype)
        # lane-broadcast to 128 (TPU min tile; same layout as the stock jax
        # flash kernel's l/m residuals)
        lse_ref[0, a * bq:(a + 1) * bq, :] = jnp.broadcast_to(
            m_i + jnp.log(l_safe), (bq, 128))

    for a in range(per):
        block(a)


def _recompute_p(q, kblk, s_scale, mask_blk, lse_col, row0, col0, window,
                 masked, live):
    s = jax.lax.dot_general(q, kblk, _NT, preferred_element_type=jnp.float32)
    if s_scale != 1.0:
        s = s * s_scale
    if mask_blk is not None:
        s = s + mask_blk
    return jnp.exp(_cut(s, row0, col0, window, masked, live) - lse_col)


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   mask_ref, dq_ref, *, scale, causal, block_q, block_k,
                   steps, dropout_p, window=None):
    bh = pl.program_id(0)
    bq, d = block_q, q_ref.shape[2]
    t = k_ref.shape[1]
    per = q_ref.shape[1] // bq
    fold = causal and _fold(scale)
    s_scale = 1.0 if fold else scale

    def block(a):       # q block a of the grid step's
        iq = _block_index(a, per, steps)
        at = slice(a * bq, (a + 1) * bq)
        q, do = q_ref[0, at, :], do_ref[0, at, :]
        lse_col, delta_col = lse_ref[0, at, 0:1], delta_ref[0, at, 0:1]
        if fold:
            q = q * scale

        def tile(carry, j, masked, live):
            c0 = _at(j, block_k)
            kblk = k_ref[0, pl.ds(c0, block_k), :]
            vblk = v_ref[0, pl.ds(c0, block_k), :]
            mask_blk = None
            if mask_ref is not None:
                mask_blk = mask_ref[0, 0:1, pl.ds(c0, block_k)] \
                    .astype(jnp.float32)
            p = _recompute_p(q, kblk, s_scale, mask_blk, lse_col, iq * bq,
                             c0, window, masked, live)
            dp = jax.lax.dot_general(do, vblk, _NT,
                                     preferred_element_type=jnp.float32)
            if dropout_p > 0.0:
                keep = _keep_mask(seed_ref, bh, *_ids(iq * bq, c0, p.shape),
                                  t, dropout_p)
                dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
            ds = p * (dp - delta_col)
            if s_scale != 1.0:
                ds = ds * s_scale
            return carry + jnp.dot(ds.astype(kblk.dtype), kblk,
                                   preferred_element_type=jnp.float32)

        dq = _walk(iq, bq, block_k, t // block_k, causal, window, False,
                   jnp.zeros((bq, d), jnp.float32), tile)
        if fold:        # ds went unscaled
            dq = dq * scale
        dq_ref[0, at, :] = dq.astype(dq_ref.dtype)

    for a in range(per):
        block(a)


def _dkv_parts(q, do, lse_col, delta_col, kb, vb, mask_blk, s_scale, row0,
               col0, window, masked, live, keep_of, dropout_p):
    """What q rows row0 .. (q with the scale on it where that is folded)
    add to the dv, then to the dk, of K rows col0 .., float32: a
    generator, so that the caller adds dv's part before dk's two products
    are there (dv is not kept beside them: 64 registers' worth at a head
    of 128)."""
    p = _recompute_p(q, kb, s_scale, mask_blk, lse_col, row0, col0, window,
                     masked, live)
    dp = jax.lax.dot_general(do, vb, _NT, preferred_element_type=jnp.float32)
    if dropout_p > 0.0:
        keep = keep_of(*_ids(row0, col0, p.shape))
        pd = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        dp = jnp.where(keep, dp / (1.0 - dropout_p), 0.0)
    else:
        pd = p
    yield jax.lax.dot_general(pd.astype(do.dtype), do, _TN,
                              preferred_element_type=jnp.float32)
    ds = p * (dp - delta_col)
    if s_scale != 1.0:
        ds = ds * s_scale
    yield jax.lax.dot_general(ds.astype(q.dtype), q, _TN,
                              preferred_element_type=jnp.float32)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    mask_ref, dk_ref, dv_ref, *, scale, causal, block_q,
                    block_k, steps, dropout_p):
    bh = pl.program_id(0)
    bk, d = block_k, k_ref.shape[2]
    s_len = q_ref.shape[1]
    per = k_ref.shape[1] // bk
    s_len_t = steps * per * bk  # kv length (hash uses row*T+col global ids)
    fold = causal and _fold(scale)

    def block(a):       # K block a of the grid step's
        jk = _block_index(a, per, steps)
        at = slice(a * bk, (a + 1) * bk)
        kblk, vblk = k_ref[0, at, :], v_ref[0, at, :]   # (Bk, D) native
        mask_blk = mask_ref[0, 0:1, at].astype(jnp.float32) \
            if mask_ref is not None else None

        def tile(carry, i, masked, live):
            rows = pl.ds(_at(i, block_q), block_q)
            q = q_ref[0, rows, :]
            dk, dv = carry
            parts = _dkv_parts(
                q * scale if fold else q, do_ref[0, rows, :],
                lse_ref[0, rows, 0:1], delta_ref[0, rows, 0:1], kblk, vblk,
                mask_blk, 1.0 if fold else scale, i * block_q, jk * bk, None,
                masked, live, lambda rows, cols: _keep_mask(
                    seed_ref, bh, rows, cols, s_len_t, dropout_p), dropout_p)
            dv = dv + next(parts)
            return dk + next(parts), dv

        z = jnp.zeros((bk, d), jnp.float32)
        dk, dv = _walk(jk, bk, block_q, s_len // block_q, causal, None, True,
                       (z, z), tile)
        dk_ref[0, at, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, at, :] = dv.astype(dv_ref.dtype)

    for a in range(per):
        block(a)


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
    the block before it again, so nothing is fetched). What a step does
    with its tile is `_span_bodies`'."""
    bkv, jk = pl.program_id(0), pl.program_id(1)
    g, r = pl.program_id(2), pl.program_id(3)
    kblk, vblk = k_ref[0], v_ref[0]
    bk, d = kblk.shape
    block_q = q_ref.shape[1]
    bounds = _tiles(jk * bk, bk, block_q, nq, causal, window, True)
    i = bounds[0] + r
    fold = causal and _fold(scale)

    @pl.when(jnp.logical_and(g == 0, r == 0))
    def _zero():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def add(masked):
        q = q_ref[0]
        parts = _dkv_parts(
            q * scale if fold else q, do_ref[0], lse_ref[0, :, 0:1],
            delta_ref[0, :, 0:1], kblk, vblk,
            mask_ref[0, 0:1, :].astype(jnp.float32)
            if mask_ref is not None else None,
            1.0 if fold else scale, i * block_q, jk * bk, window, masked,
            None, lambda rows, cols: _keep_mask(
                seed_ref, bkv * group + g, rows, cols,
                pl.num_programs(1) * bk, dropout_p), dropout_p)
        dv_acc[...] += next(parts)
        dk_acc[...] += next(parts)

    for when, masked in _span_bodies(i, bounds, causal, window):
        pl.when(when)(functools.partial(add, masked))

    @pl.when(jnp.logical_and(g == group - 1, r == span - 1))
    def _store():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _q_span(nq, nk, bk, block_q, causal, window):
    """Grid steps a K block takes over q blocks: the most any reaches."""
    return max(hi - lo for lo, _, _, hi in (
        _tiles(j * bk, bk, block_q, nq, causal, window, True)
        for j in range(nk)))


# ---------------------------------------------------------------------------
# custom-vjp wrapper
# ---------------------------------------------------------------------------

def _smem_seed_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _bound(kernel, name, mask_at, has_mask, **kw):
    """`kernel` as pallas_call hands it its refs, under `name`: the padding
    mask's ref is argument `mask_at`, None where the call has no mask."""
    def kfn(*refs):
        if not has_mask:
            refs = refs[:mask_at] + (None,) + refs[mask_at:]
        kernel(*refs, **kw)
    kfn.__name__ = name
    return kfn


# what a long sequence's whole K and V (fwd, dq) may take of VMEM
_SPAN_VMEM_BYTES = 64 * 2 ** 20


def _kv_index(group):
    """Index map of the whole K or V of the KV head that query-head row b
    belongs to (the plain call's own row: its map stays the text it
    was)."""
    if group == 1:
        return lambda b, i: (b, 0, 0)
    return lambda b, i: (b // group, 0, 0)


# what the whole K and V of a head, double-buffered, may take before the
# plain call asks for more than the compiler's default 16 MiB of scoped
# VMEM (8,192 positions of 192 + 128 in bf16 take 10 MiB and fit beside a
# query block of 256; 16,384 take 20 and were refused: PR 49)
_PLAIN_HELD_BYTES = 12 * 2 ** 20


def _named(kind, window, group, held=0):
    """pallas_call arguments of the banded and grouped calls: a name a
    trace tells the band's calls from the full layer's by (the profiler
    shows it as the instruction's), and room for the whole K and V of a
    long sequence. The plain call (no band, one query head a KV head)
    stays the program it was, unless the `held` bytes of its whole K and V
    pass the default's room: then it gets the room and still no name."""
    if _plain(window, group):
        if held <= _PLAIN_HELD_BYTES:
            return {}
        return {"compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=_SPAN_VMEM_BYTES)}
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
    rows = block_q * _per_step(s, block_q, causal)   # of q a grid step
    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              steps=s // rows, dropout_p=dropout_p, window=window)
    in_specs = [
        _smem_seed_spec(),
        pl.BlockSpec((1, rows, d), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, t, d), kv_at),
        pl.BlockSpec((1, t, dv), kv_at),
    ]
    args = [seed_arr, q3, k3, v3]
    if mask2 is not None:
        in_specs.append(
            pl.BlockSpec((1, 8, t), lambda b, i: (b // heads, 0, 0)))
        args.append(mask2)

    o, lse = pl.pallas_call(
        _bound(_fwd_kernel, "kfn", 4, mask2 is not None, **kw),
        grid=(bh, s // rows), in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, rows, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, rows, 128), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), q3.dtype),
            jax.ShapeDtypeStruct((bh, s, 128), jnp.float32),
        ],
        interpret=_interpret(),
        **_named("fwd", window, group,
                 held=2 * t * (d + dv) * k3.dtype.itemsize))(*args)
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

    rows = block_q * _per_step(s, block_q, causal)   # of q a grid step
    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              steps=s // rows, dropout_p=dropout_p, window=window)
    dq_in = [
        _smem_seed_spec(),
        pl.BlockSpec((1, rows, d), lambda b, i: (b, i, 0)),      # q
        pl.BlockSpec((1, t, d), _kv_index(group)),               # k
        pl.BlockSpec((1, t, d), _kv_index(group)),               # v
        pl.BlockSpec((1, rows, d), lambda b, i: (b, i, 0)),      # do
        pl.BlockSpec((1, rows, 128), lambda b, i: (b, i, 0)),    # lse
        pl.BlockSpec((1, rows, 128), lambda b, i: (b, i, 0)),    # delta
    ]
    dq_args = [seed_arr, q3, k3, v3, g, lse, delta]
    if mask2 is not None:
        dq_in.append(
            pl.BlockSpec((1, 8, t), lambda b, i: (b // heads, 0, 0)))
        dq_args.append(mask2)

    dq = pl.pallas_call(
        _bound(_bwd_dq_kernel, "dq_kfn", 7, mask2 is not None, **kw),
        grid=(bh, s // rows), in_specs=dq_in,
        out_specs=[pl.BlockSpec((1, rows, d), lambda b, i: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q3.dtype)],
        interpret=_interpret(), **_named("dq", window, group))(*dq_args)[0]

    if not _plain(window, group):
        dk, dv = _dkv_span(q3, k3, v3, mask2, seed_arr, g, lse, delta, scale,
                           causal, block_q, block_k, dropout_p, window, group)
        return dq, dk, dv, None, None

    cols = block_k * _per_step(t, block_k, causal)   # of K a grid step
    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              steps=t // cols, dropout_p=dropout_p)
    kv_in = [
        _smem_seed_spec(),
        pl.BlockSpec((1, s, d), lambda b, j: (b, 0, 0)),         # q full
        pl.BlockSpec((1, cols, d), lambda b, j: (b, j, 0)),      # k block
        pl.BlockSpec((1, cols, d), lambda b, j: (b, j, 0)),      # v block
        pl.BlockSpec((1, s, d), lambda b, j: (b, 0, 0)),         # do full
        pl.BlockSpec((1, s, 128), lambda b, j: (b, 0, 0)),       # lse
        pl.BlockSpec((1, s, 128), lambda b, j: (b, 0, 0)),       # delta
    ]
    kv_args = [seed_arr, q3, k3, v3, g, lse, delta]
    if mask2 is not None:
        kv_in.append(
            pl.BlockSpec((1, 8, cols), lambda b, j: (b // heads, 0, j)))
        kv_args.append(mask2)

    dk, dv = pl.pallas_call(
        _bound(_bwd_dkv_kernel, "dkv_kfn", 7, mask2 is not None, **kw),
        grid=(bh, t // cols), in_specs=kv_in,
        out_specs=[
            pl.BlockSpec((1, cols, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, cols, d), lambda b, j: (b, j, 0)),
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
    span = _q_span(nq, t // block_k, block_k, block_q, causal, window)
    kv_heads = bkv // mask2.shape[0] if mask2 is not None else 1

    def q_at(b, j, h, r):
        lo, _, _, hi = _tiles(j * block_k, block_k, block_q, nq, causal,
                              window, True)
        return b * group + h, jnp.minimum(lo + r, hi - 1), 0

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

    return pl.pallas_call(
        _bound(_bwd_dkv_span_kernel, "kfn", 7, mask2 is not None, **kw),
        grid=(bkv, t // block_k, group, span), in_specs=in_specs,
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

"""Pallas TPU fused transformer FFN: y = act(x @ W1 + b1) @ W2 + b2.

Composed, the FFN activation tier of an encoder's train step (erf-gelu
+ its saved branch predicates over bf16[B,T,4H]) is VPU work that is
materialised to HBM between the two matmuls; no cell of the benchmark
measures it. This kernel keeps the 4H
intermediate in VMEM: per (M-block, I-block) grid cell it computes
act(x_blk @ W1_blk + b1_blk) on-chip and accumulates the second matmul
into an f32 scratch, so the intermediate never exists in HBM and the
gelu runs tile-at-a-time interleaved with MXU work.

Reference equivalent: the fused FFN passes of
operators/fused/fused_feedforward_op.cc (the mechanism — one kernel for
linear+act+linear — re-expressed as a TPU Mosaic pipeline).

Backward (custom_vjp) rematerialises: only x is saved; dx/dW come from
one recompute matmul + the standard four, all left to XLA — the fwd
traffic/VPU win is where the audit says the money is.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import on_tpu

__all__ = ["fused_ffn", "can_use_fused_ffn"]


def _interpret() -> bool:
    return not on_tpu()


def _vmem_budget() -> int:
    return int(os.environ.get("PADDLE_TPU_FFN_VMEM_BUDGET",
                              14 * (1 << 20)))


def _pick_blocks(m: int, h: int, i: int,
                 itemsize: int) -> tuple[int, int] | None:
    """Largest (bm, bi) whose VMEM working set fits the budget: the f32
    (bm, h) accumulator scratch plus the double-buffered x/W1/b1/W2/b2/
    out blocks. Scaling bm (and bi) down with h is what keeps large-h
    models on the fused path instead of failing Mosaic compilation at
    runtime (ADVICE: ~16 MiB usable VMEM on v5e; 8 MiB scratch alone at
    bm=512/h=4096)."""
    budget = _vmem_budget()
    for bm in (512, 256, 128):
        if m % bm:
            continue
        for bi in (512, 256, 128):
            if i % bi:
                continue
            scratch = bm * h * 4
            blocks = 2 * itemsize * (bm * h      # x block
                                     + h * bi + bi   # W1, b1
                                     + bi * h + h    # W2, b2
                                     + bm * h)       # out block
            if scratch + blocks <= budget:
                return bm, bi
    return None


def can_use_fused_ffn(m: int, h: int, i: int, itemsize: int = 4) -> bool:
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS"):
        return False
    if os.environ.get("PADDLE_TPU_DISABLE_FFN_FUSION"):
        return False
    if not (on_tpu() or os.environ.get("PADDLE_TPU_PALLAS_INTERPRET")):
        return False
    # MXU-aligned shapes that fit the VMEM budget; fall back to the XLA
    # chain otherwise (callers pass the activation itemsize — bf16
    # fits shapes f32 cannot)
    return (m % 256 == 0 and h % 128 == 0 and i % 512 == 0
            and h <= 4096
            and _pick_blocks(m, h, i, itemsize) is not None)


def _erf_poly(z):
    """Abramowitz & Stegun 7.1.26 rational erf (|err| < 1.5e-7 in f32):
    Pallas TPU has no erf/erfc primitive, and 1.5e-7 is far inside bf16
    activation tolerance."""
    s = jnp.sign(z)
    a = jnp.abs(z)
    t = 1.0 / (1.0 + 0.3275911 * a)
    poly = t * (0.254829592 + t * (-0.284496736 + t * (
        1.421413741 + t * (-1.453152027 + t * 1.061405429))))
    return s * (1.0 - poly * jnp.exp(-a * a))


def _gelu_exact(v):
    f = v.astype(jnp.float32)
    return (0.5 * f * (1.0 + _erf_poly(f * 0.7071067811865476))
            ).astype(v.dtype)


def _gelu_tanh(v):
    """tanh-approximated gelu (the GPT-2 convention jax.nn.gelu
    approximate=True uses) — bit-matching formula, so the fused blocks
    can hold paths that train with the approximate activation."""
    f = v.astype(jnp.float32)
    c = 0.7978845608028654  # sqrt(2/pi)
    return (0.5 * f * (1.0 + jnp.tanh(c * (f + 0.044715 * f * f * f)))
            ).astype(v.dtype)


_ACTS = {
    "gelu": _gelu_exact,
    "gelu_tanh": _gelu_tanh,
    "relu": jax.nn.relu,
}


def _ffn_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref, acc_ref,
                *, act, n_i):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = jnp.dot(x_ref[...], w1_ref[...],
                preferred_element_type=jnp.float32) + b1_ref[...]
    hid = act(a).astype(x_ref.dtype)
    acc_ref[...] += jnp.dot(hid, w2_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(j == n_i - 1)
    def _emit():
        o_ref[...] = (acc_ref[...] + b2_ref[...]).astype(o_ref.dtype)


def _ffn_fwd_impl(x2, w1, b1, w2, b2, act_name, bm, bi):
    m, h = x2.shape
    i = w1.shape[1]
    n_i = i // bi
    act = _ACTS[act_name]
    return pl.pallas_call(
        functools.partial(_ffn_kernel, act=act, n_i=n_i),
        grid=(m // bm, n_i),
        in_specs=[
            pl.BlockSpec((bm, h), lambda mi, ji: (mi, 0)),
            pl.BlockSpec((h, bi), lambda mi, ji: (0, ji)),
            pl.BlockSpec((1, bi), lambda mi, ji: (0, ji)),
            pl.BlockSpec((bi, h), lambda mi, ji: (ji, 0)),
            pl.BlockSpec((1, h), lambda mi, ji: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, h), lambda mi, ji: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((m, h), x2.dtype),
        scratch_shapes=[pltpu.VMEM((bm, h), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
    )(x2, w1, b1.reshape(1, i), w2, b2.reshape(1, h))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def fused_ffn(x, w1, b1, w2, b2, act_name="gelu"):
    """x [..., H] -> [..., H]; the 4H intermediate stays in VMEM."""
    return _fused_ffn_fwd(x, w1, b1, w2, b2, act_name)[0]


def _fused_ffn_fwd(x, w1, b1, w2, b2, act_name):
    shape = x.shape
    h = shape[-1]
    x2 = x.reshape(-1, h)
    m = x2.shape[0]
    i = w1.shape[1]
    blocks = _pick_blocks(m, h, i, x.dtype.itemsize)
    if blocks is None:
        # no block shape fits VMEM (or m isn't block-aligned): run the
        # composed XLA chain rather than fail Mosaic compilation
        hid = _ACTS[act_name](x2 @ w1 + b1)
        y = (hid.astype(x.dtype) @ w2 + b2).astype(x.dtype)
    else:
        y = _ffn_fwd_impl(x2, w1, b1, w2, b2, act_name, *blocks)
    return y.reshape(shape), (x, w1, b1, w2, b2)


def _fused_ffn_bwd(act_name, res, dy):
    x, w1, b1, w2, b2 = res
    act = _ACTS[act_name]
    h = x.shape[-1]
    x2 = x.reshape(-1, h).astype(jnp.float32)
    dy2 = dy.reshape(-1, h).astype(jnp.float32)

    def chain(x2f, w1f, b1f, w2f, b2f):
        hid = act(x2f @ w1f + b1f)
        return hid @ w2f + b2f

    # one recompute matmul + the standard four, via XLA's autodiff —
    # nothing was saved between the matmuls
    _, vjp = jax.vjp(chain, x2, w1.astype(jnp.float32),
                     b1.astype(jnp.float32), w2.astype(jnp.float32),
                     b2.astype(jnp.float32))
    dx2, dw1, db1, dw2, db2 = vjp(dy2)
    return (dx2.reshape(x.shape).astype(x.dtype),
            dw1.astype(w1.dtype), db1.astype(b1.dtype),
            dw2.astype(w2.dtype), db2.astype(b2.dtype))


fused_ffn.defvjp(_fused_ffn_fwd, _fused_ffn_bwd)


# ---------------------------------------------------------------------------
# autobench gate + warmer: the fused FFN must beat the composed chain
# per shape on TPU (PR-7 satellite: no hand kernel holds a hot path by
# construction — every Pallas-vs-XLA choice routes through prefer())
# ---------------------------------------------------------------------------

def _gate_ffn(m, h, i, dtype, act="gelu"):
    import numpy as np
    dtype = jnp.dtype(dtype)
    key = ("fused_ffn", m, h, i, str(dtype), act)

    def mk(rng, r, c):
        return jnp.asarray(rng.randn(r, c) * 0.05, dtype)

    def make_args():
        rng = np.random.RandomState(0)
        return (mk(rng, m, h), mk(rng, h, i), mk(rng, 1, i)[0],
                mk(rng, i, h), mk(rng, 1, h)[0])

    def xla_chain(x, w1, b1, w2, b2):
        hid = _ACTS[act](x @ w1 + b1)
        return (hid.astype(x.dtype) @ w2 + b2).astype(x.dtype)

    cands = {
        "pallas": lambda *a: fused_ffn(*a, act),
        "xla": xla_chain,
    }
    return key, cands, make_args


def ffn_wins(m, h, i, dtype, act="gelu") -> bool:
    """On TPU: measured per-shape arbitration (persisted via the tuning
    cache); off-TPU the interpret opt-in that passed can_use runs it."""
    if not on_tpu():
        return True
    from . import autobench
    key, cands, make_args = _gate_ffn(m, h, i, dtype, act)
    return autobench.prefer(key, cands, make_args,
                            default="pallas") == "pallas"


def _warm_ffn(spec: dict) -> str:
    from . import autobench
    key, cands, make_args = _gate_ffn(
        int(spec["m"]), int(spec["h"]), int(spec["i"]),
        spec.get("dtype", "bfloat16"), spec.get("act", "gelu"))
    return autobench.prefer(key, cands, make_args, default="pallas")


def _register_warmer():
    from . import autobench
    autobench.register_warmer("fused_ffn", _warm_ffn)


_register_warmer()

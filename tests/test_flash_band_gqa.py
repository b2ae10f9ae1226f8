"""The flash kernel's band and grouped-query heads (ops/pallas_attention.py:
a lower bound beside the causal upper one in the forward and dq calls, the
dk/dv call over a K block's span of q blocks and its group's heads)
against dense XLA, in interpret mode: forward, dq, dk, dv. And the plain
call (no band, one query head a KV head) is the program it was."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.ops.pallas_attention import flash_attention


def _dense(q, k, v, window):
    B, H, S, D = q.shape
    G = H // k.shape[1]
    k, v = jnp.repeat(k, G, 1), jnp.repeat(v, G, 1)
    s = jnp.einsum("bhsd,bhtd->bhst", q, k) / math.sqrt(D)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None]
    ok = j <= i
    if window is not None:
        ok = ok & (i - j < window)
    p = jax.nn.softmax(jnp.where(ok, s, -1e30), -1)
    return jnp.einsum("bhst,bhtd->bhsd", p, v)


def _inputs(S, G, seed=0, B=2, Hkv=2, D=16):
    r = np.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)
    return (mk(B, Hkv * G, S, D), mk(B, Hkv, S, D), mk(B, Hkv, S, D),
            mk(B, Hkv * G, S, D))


# (positions, block_q, block_k, window, group): a window smaller than,
# equal to and larger than a block, positions not a multiple of the
# window, blocks of two sizes, one and eight query heads a KV head
CASES = [(256, 64, 64, 24, 1), (256, 64, 64, 64, 8), (256, 64, 64, 200, 4),
         (384, 128, 64, 100, 2), (256, 64, 128, 96, 8),
         (256, 64, 64, None, 8), (256, 128, 64, None, 2),
         (256, 64, 64, 1, 1)]


@pytest.mark.parametrize("S,bq,bk,window,G", CASES)
def test_forward_and_gradients_against_dense(S, bq, bk, window, G):
    q, k, v, w = _inputs(S, G)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=bq, block_k=bk, window=window)
    np.testing.assert_allclose(flash(q, k, v), _dense(q, k, v, window),
                               atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense(*a, window) * w),
                    (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)


def test_a_band_as_wide_as_the_sequence_is_the_triangle():
    q, k, v, _ = _inputs(256, 4)
    a = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                        window=256)
    b = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    np.testing.assert_array_equal(a, b)


def test_dropout_and_a_padding_mask_ride_the_grouped_band():
    """The keep mask is a hash of the (head, row, column): forward and
    both backward calls regenerate it, grouped and banded as plain."""
    q, k, v, w = _inputs(128, 4)
    mask = jnp.where(jnp.arange(128) < 120, 0.0, -1e30)[None, None, None, :]
    mask = jnp.broadcast_to(mask, (2, 1, 1, 128)).astype(jnp.float32)
    f = lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, mask, causal=True, dropout_p=0.25, dropout_seed=7,
        block_q=64, block_k=64, window=48) * w)
    g = jax.grad(f, (0, 1, 2))(q, k, v)
    # a directional derivative against finite differences of the same
    # (deterministic) dropped function
    r = np.random.RandomState(1)
    d = [jnp.asarray(r.randn(*a.shape), jnp.float32) for a in (q, k, v)]
    eps = 1e-2
    up = f(*(a + eps * x for a, x in zip((q, k, v), d)))
    dn = f(*(a - eps * x for a, x in zip((q, k, v), d)))
    want = float(up - dn) / (2 * eps)
    got = float(sum(jnp.sum(a * x) for a, x in zip(g, d)))
    assert abs(got - want) < 2e-2 * max(1.0, abs(want))


def test_a_band_needs_causal_self_attention():
    q, k, v, _ = _inputs(128, 1)
    with pytest.raises(ValueError, match="band"):
        flash_attention(q, k, v, causal=False, window=16)
    with pytest.raises(ValueError, match="whole groups"):
        flash_attention(jnp.concatenate([q, q[:, :1]], 1), k, v, causal=True)


@pytest.mark.parametrize("causal", [True, False])
def test_the_plain_call_is_the_program_it_was(causal):
    """No band and one query head a KV head: the three pallas_calls (and
    three they stay: the benchmark's roofline takes their mean) carry no
    name and no compiler parameters, and the dk/dv call is the
    whole-sequence kernel. Its results equal the grouped path's at G = 1
    forced through the span kernel, to the last bit: both kernels add a
    K tile's q blocks in the order of the sequence, and the mask the span
    kernel puts on the tiles past the diagonal changes no score."""
    q, k, v, w = _inputs(256, 1)
    f = lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=causal, block_q=64, block_k=64) * w)
    text = jax.jit(jax.grad(f, (0, 1, 2))).lower(q, k, v).as_text()
    assert "flash_" not in text
    assert pa._named("fwd", None, 1) == {}
    assert str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, k, v)).count(
        "pallas_call") == 3
    if causal:
        # the same numbers whichever dk/dv kernel: blocks are summed in
        # the same order
        got = jax.grad(f, (0, 1, 2))(q, k, v)
        span = pa._dkv_span
        seen = []
        try:
            pa._dkv_span = lambda *a: seen.append(1) or span(*a)
            wide = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=64, block_k=64, window=256)
                * w), (0, 1, 2))(q, k, v)
        finally:
            pa._dkv_span = span
        assert seen
        for a, b in zip(got, wide):
            np.testing.assert_array_equal(a, b)

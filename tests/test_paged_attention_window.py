"""Paged attention from a first live position, over a ring
(`ops/paged_attention.py::paged_attention_xla`: `first`, `ring`) against
attention written out densely over the logical positions.
"""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import paged_attention as pa

PS, HKV, G, D = 4, 2, 4, 128


def _ring_case(ctx, first, ring, seed=0, pages=40):
    """Pools whose ring pages hold each slot's positions as a serving
    program leaves them: position t in entry (t // ps) mod ring, older
    tenants of a page overwritten, the rest of the pool noise."""
    rng = np.random.RandomState(seed)
    S = len(ctx)
    k_seq = rng.randn(S, max(ctx), HKV, D).astype(np.float32)
    v_seq = rng.randn(S, max(ctx), HKV, D).astype(np.float32)
    k_pool = rng.randn(2, pages, PS, HKV, D).astype(np.float32)
    v_pool = rng.randn(2, pages, PS, HKV, D).astype(np.float32)
    table = rng.permutation(pages - 1)[:S * ring].reshape(S, ring)
    for s in range(S):
        for t in range(ctx[s]):
            e = (t // PS) % ring
            k_pool[1, table[s, e], t % PS] = k_seq[s, t]
            v_pool[1, table[s, e], t % PS] = v_seq[s, t]
    q = rng.randn(S, HKV * G, D).astype(np.float32)
    return q, k_seq, v_seq, k_pool, v_pool, table.astype(np.int32)


def _dense(q, k_seq, v_seq, ctx, first):
    """Attention of each slot over its positions first .. ctx - 1."""
    S, H, d = q.shape
    out = np.zeros_like(q)
    for s in range(S):
        for h in range(H):
            k = k_seq[s, first[s]:ctx[s], h // G]
            v = v_seq[s, first[s]:ctx[s], h // G]
            sc = k @ q[s, h] / math.sqrt(d)
            p = np.exp(sc - sc.max())
            out[s, h] = (p / p.sum()) @ v
    return out


CTX = [1, 3, 4, 5, 8, 9, 11, 12, 13, 23, 40, 37]


# a window of 8 positions over pages of 4 is a ring of 3; of 16, of 5
@pytest.mark.parametrize("window, ring", [(8, 3), (16, 5)])
def test_the_ring_walk_is_dense_attention_over_the_window(window, ring):
    ctx = np.asarray(CTX, np.int32)
    first = np.maximum(ctx - window, 0).astype(np.int32)
    # `first` falls in the middle of a page (window 8, ctx 13: 5; 23: 15;
    # 37: 29), at a page's start (12: 4; 40: 32) and at 0 (never left it)
    assert {int(f) % PS for f in first} >= {0, 1, 3}
    q, k_seq, v_seq, kp, vp, table = _ring_case(CTX, first, ring, pages=80)
    want = _dense(q, k_seq, v_seq, ctx, first)
    got = pa.paged_attention_xla(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
        jnp.asarray(ctx), layer=1, first=jnp.asarray(first), ring=ring)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


def test_first_without_a_ring_masks_a_plain_table_below_it():
    """A full table (entry e is page e) attended from `first` on: what the
    pages before first // ps hold does not count (huge rows here)."""
    ctx = np.asarray([30, 17, 9, 6], np.int32)
    first = np.asarray([13, 16, 0, 5], np.int32)
    q, k_seq, v_seq, kp, vp, table = _ring_case(list(ctx), first, 8)
    for s in range(len(ctx)):
        for e in range(first[s] // PS):
            kp[1, table[s, e]] = 1e4
            vp[1, table[s, e]] = 1e4
    got = pa.paged_attention_xla(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
        jnp.asarray(ctx), layer=1, first=jnp.asarray(first))
    np.testing.assert_allclose(np.asarray(got),
                               _dense(q, k_seq, v_seq, ctx, first),
                               atol=2e-5, rtol=2e-5)


def test_ring_entries_that_hold_no_live_position_do_not_count():
    """A slot of context 5 in a ring of 3 has written entries 0 and 1
    only: what entry 2 holds (a slot's last tenant's rows) is masked."""
    ctx = np.asarray([5, 2], np.int32)
    first = np.zeros((2,), np.int32)
    q, k_seq, v_seq, kp, vp, table = _ring_case(list(ctx), first, 3)
    kp[1, table[:, 2]] = vp[1, table[:, 2]] = 1e4
    kp[1, table[1, 1]] = vp[1, table[1, 1]] = 1e4
    got = pa.paged_attention_xla(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table),
        jnp.asarray(ctx), layer=1, first=jnp.asarray(first), ring=3)
    np.testing.assert_allclose(np.asarray(got),
                               _dense(q, k_seq, v_seq, ctx, first),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kw", [{"ring": 3}, {"ring": 4, "first": True},
                                {"ring": 0, "first": True}])
def test_a_ring_needs_a_first_position_and_fits_the_table(kw):
    q = jnp.zeros((1, HKV, D)); pool = jnp.zeros((8, PS, HKV, D))
    table = jnp.zeros((1, 3), jnp.int32); ctx = jnp.ones((1,), jnp.int32)
    if kw.get("first"):
        kw = {**kw, "first": jnp.zeros((1,), jnp.int32)}
    with pytest.raises(ValueError, match=f"a ring of {kw['ring']} entries"):
        pa.paged_attention_xla(q, pool, pool, table, ctx, **kw)

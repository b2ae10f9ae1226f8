"""The flash kernel's tile schedule (ops/pallas_attention.py: `_walk`, which
the three looping kernels fold their tile bodies over, `_span_bodies`,
which the dk/dv span kernel takes its step's from, and `flash_schedule`,
a recorder over both): a tile the mask leaves nothing of is never
multiplied, one its edge does not cross pays no mask arithmetic, an edge
tile of a square tiling is taken out of the loop, and a short sequence is
one grid step a head with every block index Python's. The schedule alone
at the training cells' real shapes (no chip, no kernel), then the kernels
in interpret mode at small shapes against dense XLA, over the cases the
bounds can get wrong."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.ops.pallas_attention import flash_attention, flash_schedule
from test_flash_band_gqa import _dense, _inputs


def _allowed(r0, nr, c0, nc, window):
    r = r0 + np.arange(nr)[:, None]
    c = c0 + np.arange(nc)[None]
    ok = c <= r
    return ok if window is None else ok & (r - c < window)


def _pairs(t, window):
    w = min(window or t, t)
    return w * (w + 1) // 2 + (t - w) * w


# the three training cells' calls and the served prefill's: (positions,
# band, query heads a KV head,
# the most score elements multiplied a pair the mask lets through: edge
# tiles are multiplied whole, as every tile was, so the tiling's own 1.5,
# 1.5 and 1.0625: walked in sub-tiles of 128 the backward calls read 1.124,
# 1.125 and 1.016 for 0.13 ms a layer of the 350M step and 0.45 of
# Mellum's, under both cells' bounds, and the sub-tiles went: PERF.md)
CELLS = {"gpt_350m_t1024_d64": (1024, None, 1, 1.5),
         "mellum_band_t8192_d128": (8192, 1024, 8, 1.5),
         "mellum_full_t8192_d128": (8192, None, 8, 1.0625),
         # the served windowed prefill's longest bucket (PR 50; its
         # forward call alone runs): a band of four tiles has three whole
         # and two edge tiles a row block
         "trinity_band_t16384_d128": (16384, 2048, 8, 1.25),
         "trinity_full_t16384_d128": (16384, None, 8, 1.03125)}


@pytest.mark.parametrize("call", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize("cell", CELLS)
def test_flash_multiplies_no_wholly_masked_tile(cell, call):
    """At the blocks the call chooses for itself and through the kernel
    the call runs (Mellum's dk/dv is the span kernel's): nothing visited
    that the mask leaves nothing of, every pair the mask lets through
    visited once, no more multiplied than the tiling's edge tiles make it,
    and the mask's arithmetic on exactly the visits an edge crosses, but
    for the full layer's dk/dv, whose one body a grid step masks every
    tile (a second, unmasked, body was slower on the chip)."""
    t, window, group, most = CELLS[cell]
    block = pa._auto_block_q(t)
    assert block == pa._auto_block_k(t) == 512
    visits = flash_schedule(t, t, block, block, True, window, call, group)
    all_masked = call == "dkv" and group > 1 and window is None
    seen = 0
    for r0, nr, c0, nc, masked in visits:
        ok = _allowed(r0, nr, c0, nc, window)
        assert ok.any(), f"a wholly masked visit {(r0, nr, c0, nc)}"
        assert masked == (all_masked or not ok.all()), (r0, nr, c0, nc)
        seen += int(ok.sum())
    assert seen == _pairs(t, window)            # all of them (none twice:
    assert len(set(v[:4] for v in visits)) == len(visits)   # below)
    assert sum(v[1] * v[3] for v in visits) / seen <= most


# (positions, block_q, block_k, band, query heads a KV head): square
# tilings and others, bands that are no multiple of a tile, the looping
# dk/dv kernel and the span kernel
TILINGS = [(256, 64, 64, None, 1), (256, 64, 64, 48, 1),
           (256, 64, 64, 100, 4), (256, 64, 64, 130, 1),
           (256, 64, 64, 1, 1), (256, 64, 64, 256, 4),
           (64, 64, 64, None, 1), (64, 64, 64, 5, 1),
           (256, 128, 64, 100, 1), (256, 64, 128, 96, 1),
           (256, 64, 64, None, 4), (512, 256, 256, 200, 1)]


@pytest.mark.parametrize("call", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize("S,bq,bk,window,G", TILINGS)
def test_the_walk_covers_the_mask_once(S, bq, bk, window, G, call):
    """Every pair the mask lets through is visited once and nothing the
    mask leaves nothing of; a square tiling, and the span kernel under a
    band, masks exactly the visits an edge crosses, another masks every
    tile (one loop, or one body a grid step, as it always was)."""
    ok = _allowed(0, S, 0, S, window)
    seen = np.zeros((S, S), int)
    span = call == "dkv" and (window is not None or G > 1)
    exact = window is not None if span else bq == bk
    for r0, nr, c0, nc, masked in flash_schedule(S, S, bq, bk, True, window,
                                                 call, G):
        part = ok[r0:r0 + nr, c0:c0 + nc]
        assert part.any() and (masked or part.all())
        assert not exact or masked == (not part.all())
        seen[r0:r0 + nr, c0:c0 + nc] += 1
    assert seen.max() == 1 and (seen[ok] == 1).all()


def test_a_call_without_a_mask_has_one_loop_and_no_edge():
    """Non-causal: every tile, none masked, the bounds Python's own (the
    kernels then emit the one loop they always had)."""
    assert pa._tiles(0, 64, 64, 4, False, None, False) == (0, 0, 4, 4)
    assert pa._per_step(512, 512, False) == 1
    for call, group in (("fwd", 1), ("dkv", 1), ("dkv", 4)):
        visits = flash_schedule(256, 256, 64, 64, False, None, call, group)
        assert len(visits) == 16 and not any(v[4] for v in visits)


# (positions, tile, band, query heads a KV head): bands that are no
# multiple of a tile, of one position, as wide as the sequence; a sequence
# of one tile
KERNEL_CASES = [(64, 32, 12, 1), (96, 32, 40, 4), (64, 32, 1, 4),
                (64, 32, 64, 1), (32, 32, None, 4), (512, 256, 200, 1)]
# the same with every block index traced (a grid step a block), as a
# sequence longer than `_ONE_STEP` is walked: a triangle of two and of
# three tiles a side, whose tiles past the diagonal are spelt out under
# `live` and not looped over, and of four, which are
TRACED = [(128, 32, 50, 4), (128, 32, None, 1), (64, 32, 1, 1),
          (96, 32, 70, 1), (64, 32, None, 1), (96, 32, None, 1),
          (96, 32, None, 4)]


@pytest.mark.parametrize("S,tile,window,G,one_step", [
    c + (1024,) for c in KERNEL_CASES] + [c + (0,) for c in TRACED])
def test_the_kernels_against_dense(monkeypatch, S, tile, window, G,
                                   one_step):
    monkeypatch.setattr(pa, "_ONE_STEP", one_step)
    q, k, v, w = _inputs(S, G, B=1, Hkv=1)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=tile, block_k=tile, window=window)
    np.testing.assert_allclose(flash(q, k, v), _dense(q, k, v, window),
                               atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense(*a, window) * w),
                    (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("window,G,one_step", [
    (None, 1, 1024), (20, 4, 0), (40, 1, 0)])
def test_dropout_and_padding_do_not_move_with_the_tiling(monkeypatch, window,
                                                         G, one_step):
    """The keep mask is a hash of the global (head, row, column), and the
    padding mask is added on every tile, masked or not: tiles of 32 and
    tiles of 16 drop the same scores, forward and in all three gradients,
    with Python's block indices and traced."""
    monkeypatch.setattr(pa, "_ONE_STEP", one_step)
    q, k, v, w = _inputs(64, G, B=1, Hkv=1)
    mask = jnp.where(jnp.arange(64) < 57, 0.0, -1e30)
    mask = jnp.broadcast_to(mask, (1, 1, 1, 64)).astype(jnp.float32)

    def run(tile):
        f = lambda q, k, v: flash_attention(
            q, k, v, mask, causal=True, dropout_p=0.25, dropout_seed=11,
            block_q=tile, block_k=tile, window=window)
        return (f(q, k, v),) + jax.grad(
            lambda *a: jnp.sum(f(*a) * w), (0, 1, 2))(q, k, v)

    wide, narrow = run(32), run(16)
    plain = flash_attention(q, k, v, mask, causal=True, block_q=32,
                            block_k=32, window=window)
    assert float(jnp.abs(wide[0] - plain).max()) > 1e-2       # it did drop
    for name, a, b in zip(("o", "dq", "dk", "dv"), wide, narrow):
        np.testing.assert_allclose(a, b, atol=3e-5, err_msg=name)


def test_the_scale_rides_q_only_where_that_is_exact(monkeypatch):
    """1/8 at a head of 64 is a power of two and goes onto q; 128 ** -0.5
    is not and stays on the float32 scores. Folded or not: the same
    numbers, to the last bit of bf16 products summed in float32."""
    assert pa._fold(64 ** -0.5) and pa._fold(1.0) and pa._fold(2.0 ** -10)
    assert not pa._fold(128 ** -0.5) and not pa._fold(192 ** -0.5)
    q, k, v, w = (a.astype(jnp.bfloat16) for a in _inputs(128, 2, B=1, D=64))

    def run():
        f = lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64, window=100)
        return (f(q, k, v),) + jax.grad(lambda *a: jnp.sum(
            (f(*a) * w).astype(jnp.float32)), (0, 1, 2))(q, k, v)

    folded = run()
    monkeypatch.setattr(pa, "_fold", lambda scale: False)
    for name, a, b in zip(("o", "dq", "dk", "dv"), folded, run()):
        np.testing.assert_array_equal(a, b, err_msg=name)

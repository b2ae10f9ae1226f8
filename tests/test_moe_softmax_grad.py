"""`dropless_moe_ffn` under `jax.grad` with a softmax router and a strict
share of the experts held: both spellings of the grouped products against
a dense float64 spelling, zero gradient for the experts that live
elsewhere, and a router gradient from the normalised weights of ALL the
chosen."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.parallel import moe
from paddle_tpu.parallel.moe import dropless_moe_ffn, softmax_topk_route

N, D, E, F, K = 64, 32, 8, 16, 2
HELD = (1, 4, 6)


def _weights(seed=0):
    r = np.random.RandomState(seed)
    mk = lambda scale, *s: jnp.asarray(r.randn(*s) * scale, jnp.float32)
    return (mk(1.0, N, D), mk(0.3, D, E), mk(0.2, E, D, F), mk(0.2, E, D, F),
            mk(0.2, E, F, D), mk(1.0, N, D))


def _dense64(h, wg, w1, w3, w2, ct, held=HELD, norm=True):
    """The held part of the layer, every expert on every row, float64."""
    h, wg, w1, w3, w2, ct = (np.asarray(a, np.float64)
                             for a in (h, wg, w1, w3, w2, ct))
    with jax.enable_x64():
        def f(h, wg, w1, w3, w2):
            p = jax.nn.softmax(h @ wg, -1)
            g, sel = jax.lax.top_k(p, K)
            if norm:
                g = g / g.sum(-1, keepdims=True)
            y = 0.0
            for e in held:
                we = jnp.sum(jnp.where(sel == e, g, 0.0), -1)
                y = y + we[:, None] * (
                    (jax.nn.silu(h @ w1[e]) * (h @ w3[e])) @ w2[e])
            return jnp.sum(y * ct), y
        (_, y), grads = jax.value_and_grad(f, (0, 1, 2, 3, 4), has_aux=True)(
            *(jnp.asarray(a, jnp.float64) for a in (h, wg, w1, w3, w2)))
        return np.asarray(y), [np.asarray(g) for g in grads]


@pytest.mark.parametrize("impl", ["gmm", "dense"])
def test_gradients_of_a_strict_share_against_dense_float64(impl):
    h, wg, w1, w3, w2, ct = _weights()
    idx = jnp.asarray(HELD)

    def f(h, wg, w1, w3, w2):
        y, sel = dropless_moe_ffn(
            h, wg, None, w1[idx], w3[idx], w2[idx], top_k=K,
            experts_held=HELD, impl=impl, route="softmax")
        return jnp.sum(y * ct), (y, sel)

    (_, (y, sel)), grads = jax.value_and_grad(
        f, (0, 1, 2, 3, 4), has_aux=True)(h, wg, w1, w3, w2)
    want_y, want = _dense64(h, wg, w1, w3, w2, ct)
    np.testing.assert_allclose(y, want_y, atol=5e-6)
    for name, a, b in zip(("h", "wg", "w1", "w3", "w2"), grads, want):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
    # an expert that lives elsewhere gets no gradient, to the bit; a held
    # one does
    away = [e for e in range(E) if e not in HELD]
    for g in grads[2:]:
        assert not np.asarray(g)[away].any()
        assert np.asarray(g)[list(HELD)].any()
    # the router's gradient is not the held experts' alone: a row all of
    # whose chosen experts live elsewhere moves nothing, a row with one
    # held and one away moves BOTH their columns (the normalisation)
    sel = np.asarray(sel)
    mixed = [n for n in range(N)
             if len(set(sel[n]) & set(HELD)) == 1]
    assert mixed
    assert np.abs(np.asarray(grads[1])[:, away]).max() > 0


def test_the_softmax_router_normalises_over_all_it_chose():
    h, wg, *_ = _weights(1)
    sel, g = softmax_topk_route(h, wg, None, K)
    p = jax.nn.softmax(h @ wg, -1)
    top, want = jax.lax.top_k(p, K)
    np.testing.assert_array_equal(sel, want)
    np.testing.assert_allclose(g.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(g, top / top.sum(-1, keepdims=True),
                               atol=1e-6)
    _, raw = softmax_topk_route(h, wg, None, K, norm_topk=False)
    np.testing.assert_allclose(raw, top, atol=1e-7)
    with pytest.raises(ValueError, match="no selection bias"):
        softmax_topk_route(h, wg, jnp.zeros((E,)), K)


def test_the_shares_parts_add_up_forward_and_backward():
    """Over a partition of the experts the parts add up to the whole
    layer, and so do the gradients of the input and the router."""
    h, wg, w1, w3, w2, ct = _weights(2)
    shares = [(0, 1, 2), (3, 4), (5, 6, 7)]

    def part(held):
        idx = jnp.asarray(held)
        return lambda h, wg: jnp.sum(dropless_moe_ffn(
            h, wg, None, w1[idx], w3[idx], w2[idx], top_k=K,
            experts_held=held, impl="gmm", route="softmax")[0] * ct)

    whole = jax.value_and_grad(part(tuple(range(E))), (0, 1))(h, wg)
    parts = [jax.value_and_grad(part(s), (0, 1))(h, wg) for s in shares]
    np.testing.assert_allclose(sum(p[0] for p in parts), whole[0],
                               rtol=1e-5)
    for i in (0, 1):
        np.testing.assert_allclose(sum(p[1][i] for p in parts), whole[1][i],
                                   atol=2e-5)


def test_the_backward_moves_rows_by_gathers_only():
    """A gather's transpose is a scatter-add of N k rows; both moves of
    rows are permutations, so their cotangents are gathers too."""
    h, wg, w1, w3, w2, ct = _weights(3)
    idx = jnp.asarray(HELD)
    f = lambda h: jnp.sum(dropless_moe_ffn(
        h, wg, None, w1[idx], w3[idx], w2[idx], top_k=K, experts_held=HELD,
        impl="gmm", route="softmax")[0] * ct)
    import re
    text = str(jax.make_jaxpr(jax.grad(f))(h))
    # (the experts' row counts are a bincount: an integer scatter-add)
    assert "scatter-add" in text
    assert not re.search(rf":f32\[\d+,{D}\] = scatter", text)


def test_tgmm_tiles_cut_the_output():
    tm, tk, tn = moe._tgmm_tiles(131072, 2304, 896)
    assert 131072 % tm == 0 and 2304 % tk == 0 and 896 % tn == 0
    assert tk * tn * 4 <= 4 * 2 ** 20       # the float32 accumulator
    assert moe._tgmm_tiles(128, 32, 16) == (128, 32, 16)

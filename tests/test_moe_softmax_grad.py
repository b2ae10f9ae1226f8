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


def _weights(seed=0, n=N):
    r = np.random.RandomState(seed)
    mk = lambda scale, *s: jnp.asarray(r.randn(*s) * scale, jnp.float32)
    return (mk(1.0, n, D), mk(0.3, D, E), mk(0.2, E, D, F), mk(0.2, E, D, F),
            mk(0.2, E, F, D), mk(1.0, n, D))


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


# ---------------------------------------------------------------------------
# A strict share's sorted arrays hold `held_rows_bound` rows, not N k
# (`_bounded_rows`): sizes at which the bound is below N k.
# ---------------------------------------------------------------------------

BN, BHELD = 512, (1, 6)                 # N k = 1,024 pairs, 512 rows
BM = moe.held_rows_bound(BN, K, len(BHELD), E)


def _bounded_weights(seed=4):
    return _weights(seed, BN)


def _router_onto(held_pairs):
    """A router (replaced as the benchmark's fault tools replace one: this
    module's attribute, read at call time) whose CHOICE is fixed, with
    exactly `held_pairs` pairs on BHELD; the weights are the softmax's
    over the chosen, so the router's gradient still flows."""
    sel = np.zeros((BN, K), np.int32)
    sel[:] = (0, 2)
    sel[:held_pairs // 2] = BHELD
    if held_pairs % 2:
        sel[held_pairs // 2] = (BHELD[0], 0)
    sel = jnp.asarray(sel)

    def route(h, wg, bias, top_k, norm_topk=True, scale=1.0):
        p = jax.nn.softmax(h @ wg, axis=-1)
        g = jnp.take_along_axis(p, sel, axis=-1)
        return sel, g / jnp.sum(g, axis=-1, keepdims=True) * scale
    return route


def _share(h, wg, w1, w3, w2, ct):
    idx = jnp.asarray(BHELD)

    def f(h, wg, w1, w3, w2):
        y, sel = dropless_moe_ffn(
            h, wg, None, w1[idx], w3[idx], w2[idx], top_k=K,
            experts_held=BHELD, impl="gmm", route="softmax")
        return jnp.sum(y * ct), (y, sel)
    (_, (y, sel)), grads = jax.value_and_grad(
        f, (0, 1, 2, 3, 4), has_aux=True)(h, wg, w1, w3, w2)
    return y, np.asarray(sel), grads


@pytest.mark.parametrize("routing,held_pairs,branch,dtype", [
    ("seeded", None, "short", "float32"),
    ("exactly_the_bound", BM, "short", "float32"),
    ("one_over_the_bound", BM + 1, "whole", "float32"),
    ("seeded", None, "short", "bfloat16")])
def test_bounded_rows_equal_whole_rows(monkeypatch, routing, held_pairs,
                                       branch, dtype):
    """The same work: with the sorted arrays at the bound's rows, the
    output and the gradients of the experts' weights are the whole-size
    path's bit for bit, and so is the input's gradient through the experts.
    The router's weights' cotangent is a sum over D of the same products,
    taken a row at a time in sorted order where the whole-size einsum's
    transpose takes a token's k rows at once: equal to float32 rounding
    (and with it the router's part of the input's gradient). One pair over
    the bound takes the whole-size path: no pair is dropped. In bfloat16
    the bounded arrays hand their sum back in bfloat16 and gather its
    cotangent so, which is the cast the layer makes anyway."""
    h, wg, w1, w3, w2, ct = _bounded_weights()
    h, w1, w3, w2 = (a.astype(dtype) for a in (h, w1, w3, w2))
    eps = float(jnp.finfo(dtype).eps)
    assert BM == 512 < BN * K
    if held_pairs is not None:
        monkeypatch.setattr(moe, "softmax_topk_route",
                            _router_onto(held_pairs))
    y, sel, grads = _share(h, wg, w1, w3, w2, ct)
    n_held = int(np.isin(sel, BHELD).sum())
    assert (n_held <= BM) == (branch == "short")
    if held_pairs is not None:
        assert n_held == held_pairs
    # the same layer with no bound: today's text, every array N k rows
    bound = moe.held_rows_bound
    monkeypatch.setattr(moe, "held_rows_bound", lambda N, k, Eh, E: N * k)
    want_y, want_sel, want = _share(h, wg, w1, w3, w2, ct)
    np.testing.assert_array_equal(sel, want_sel)
    np.testing.assert_array_equal(y, want_y)
    assert np.abs(np.asarray(want_y)).max() > 0.1
    for name, a, b in zip(("w1", "w3", "w2"), grads[2:], want[2:]):
        np.testing.assert_array_equal(a, b, err_msg=name)
        assert np.asarray(b)[list(BHELD)].any()
    for name, a, b in zip(("h", "wg"), grads[:2], want[:2]):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        np.testing.assert_allclose(
            a, b, rtol=0, atol=8 * eps * np.abs(b).max(), err_msg=name)
    # the input's gradient through the experts alone (the router's weights
    # held constant): bit for bit
    route = moe.softmax_topk_route
    monkeypatch.setattr(moe, "softmax_topk_route", lambda *a, **kw: tuple(
        jax.lax.stop_gradient(x) for x in route(*a, **kw)))
    _, _, frozen_whole = _share(h, wg, w1, w3, w2, ct)
    monkeypatch.setattr(moe, "held_rows_bound", bound)
    _, _, frozen = _share(h, wg, w1, w3, w2, ct)
    np.testing.assert_array_equal(frozen[0], frozen_whole[0])
    assert not np.asarray(frozen[1]).any()


@pytest.mark.parametrize("shape,want", [
    ((16384, 8, 64, 64), 131072),           # every expert held: N k
    ((16384, 8, 16, 64), 65536),            # the Mellum cell: half
    ((BN, K, 2, E), 512),                   # twice a quarter of 1,024
    ((64, 2, 3, 8), 128),                   # 96 rounds past N k: N k
    ((4096, 6, 24, 128), 9216),             # twice 3/16 of 24,576
    ((4096, 7, 5, 64), 4608),               # 4,480 rounded up to 512
    ((4096, 8, 100, 128), 32768)])          # twice 78% is all of them
def test_held_rows_bound_is_a_multiple_of_512_no_larger_than_all(shape,
                                                                  want):
    N, k, Eh, E_ = shape
    got = moe.held_rows_bound(N, k, Eh, E_)
    assert got == want
    assert got <= N * k and (got % 512 == 0 or got == N * k)
    assert got >= min(N * k, N * k * Eh // E_)


def _inside(eqn):
    """The jaxprs an equation holds; a kernel's body is not the layer's."""
    return [] if eqn.primitive.name == "pallas_call" \
        else list(jax.core.jaxprs_in_params(eqn.params))


def _arrays_of(jaxpr, shape, into=None):
    """Primitives of `jaxpr` (and the jaxprs inside it) that write an array
    of `shape`. An equation that holds jaxprs is what is inside it; a
    mask's or a zero's broadcast is no array (it fuses into its reader)."""
    into = [] if into is None else into
    for eqn in jaxpr.eqns:
        inside = _inside(eqn)
        for sub in inside:
            _arrays_of(sub, shape, into)
        if not inside and eqn.primitive.name != "broadcast_in_dim":
            into += [eqn.primitive.name for v in eqn.outvars
                     if getattr(v.aval, "shape", None) == shape]
    return into


def _conds(jaxpr, into=None):
    into = [] if into is None else into
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            into.append(eqn)
        for sub in _inside(eqn):
            _conds(sub, into)
    return into


@pytest.mark.parametrize("held,conds", [(None, 0), (BHELD, 1)])
def test_a_cond_only_where_a_strict_share_is_held(held, conds):
    """With every expert held (every serving cell) the layer traces to the
    text it had: no `cond`, nothing sliced to a bound."""
    h, wg, w1, w3, w2, _ct = _bounded_weights()
    idx = jnp.arange(E) if held is None else jnp.asarray(held)
    jaxpr = jax.make_jaxpr(lambda h: dropless_moe_ffn(
        h, wg, None, w1[idx], w3[idx], w2[idx], top_k=K, experts_held=held,
        impl="gmm", route="softmax")[0])(h).jaxpr
    assert len(_conds(jaxpr)) == conds
    assert bool(_arrays_of(jaxpr, (BM, F))) == bool(conds)
    assert _arrays_of(jaxpr, (BN * K, F))


@pytest.mark.parametrize("width,accepted", [
    (D, ["gather", "select_n"]), (F, [])])
def test_the_short_branch_holds_no_whole_size_array_but_the_fan_outs(
        width, accepted):
    """Of N k rows and width D the short branch writes one array forward
    and one backward, what fans back out to every pair's slot: a gather
    through the inverse permutation and the select that zeroes the pairs
    held elsewhere (the weighted sum over a token's k pairs; the sum of
    its k cotangents). Two primitives each, which the compiler may fuse;
    no third is accepted, because each further one is 0.6 GB written and
    read at Mellum2's sizes. Of width F, nothing. Outside the `cond`s,
    nothing of either width: a `cond` differentiated as it stands would
    write the whole branch's residuals as zeros there."""
    h, wg, w1, w3, w2, ct = _bounded_weights()
    idx = jnp.asarray(BHELD)
    f = lambda h, w1, w3, w2: jnp.sum(dropless_moe_ffn(
        h, wg, None, w1[idx], w3[idx], w2[idx], top_k=K, experts_held=BHELD,
        impl="gmm", route="softmax")[0] * ct)
    jaxpr = jax.make_jaxpr(jax.grad(f, (0, 1, 2, 3)))(h, w1, w3, w2).jaxpr
    conds = _conds(jaxpr)
    assert len(conds) == 2                  # the forward's, the backward's
    shape = (BN * K, width)
    for name, eqn in zip(("forward", "backward"), conds):
        whole, short = eqn.params["branches"]       # (false, true)
        assert sorted(_arrays_of(short.jaxpr, shape)) == accepted, name
        assert len(_arrays_of(whole.jaxpr, shape)) > len(accepted), name
    inside = sum(len(_arrays_of(b.jaxpr, shape)) for eqn in conds
                 for b in eqn.params["branches"])
    assert len(_arrays_of(jaxpr, shape)) == inside

"""The dropless, sigmoid-routed expert layer (parallel/moe.py) against the
plain reference's layer (benchmark/reference/lfm2_moe.py), float32 on the
CPU. Tolerance 2e-5 absolute on outputs of size ~0.1: both sides are
float32 sums of the same products in another order."""
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as ref
from paddle_tpu.parallel.moe import dropless_moe_ffn, sigmoid_topk_route

N, D, F, E, K = 24, 32, 48, 8, 2
SIZES = {"num_experts": E, "num_experts_per_tok": K, "norm_topk_prob": True,
         "use_expert_bias": True, "routed_scaling_factor": 1.0}
IMPLS = ["dense", "gmm"]


def _layer(seed=0, bias_std=0.1, wg_scale=1.0):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    return f(N, D), {"wg": wg_scale * f(D, E) / np.sqrt(D),
                     "bias": bias_std * f(E), "w1": 0.2 * f(E, D, F),
                     "w3": 0.2 * f(E, D, F), "w2": 0.2 * f(E, F, D)}


def _program(h, p, **kw):
    return dropless_moe_ffn(h, p["wg"], p["bias"], p["w1"], p["w3"], p["w2"],
                            top_k=K, **kw)


def _reference(h, p, **kw):
    return ref.moe_layer(h, p, SIZES, ref._mm("f32"), **kw)[0]


@pytest.mark.parametrize("impl", IMPLS)
def test_layer_agrees_with_the_reference(impl):
    h, p = _layer()
    y, sel = _program(h, p, impl=impl)
    np.testing.assert_allclose(y, _reference(h, p), atol=2e-5)
    assert sel.shape == (N, K) and sel.dtype == jnp.int32


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("routing", ["all_to_one", "one_gets_none"])
def test_no_pair_is_dropped_under_the_most_uneven_routing(impl, routing):
    h, p = _layer(seed=1)
    bias = np.zeros(E, np.float32)
    if routing == "all_to_one":
        bias[3], bias[5] = 50.0, 40.0       # every token: experts 3 and 5
    else:
        bias[2] = -50.0                     # nobody reaches expert 2
    p["bias"] = jnp.asarray(bias)
    y, sel = _program(h, p, impl=impl)
    if routing == "all_to_one":
        assert set(np.asarray(sel).ravel()) == {3, 5}
    else:
        assert 2 not in set(np.asarray(sel).ravel())
    # a capacity-based layer would have dropped most of these pairs
    np.testing.assert_allclose(y, _reference(h, p), atol=2e-5)


def test_the_bias_chooses_and_does_not_weigh():
    h, p = _layer(seed=2, bias_std=0.5)
    sel, g = sigmoid_topk_route(h, p["wg"], p["bias"], K)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(h) @ np.asarray(p["wg"]))))
    biased = s + np.asarray(p["bias"])
    want = np.argsort(-biased, axis=1)[:, :K]
    assert np.array_equal(np.sort(sel, 1), np.sort(want, 1))
    # ... and differs from the choice by score alone for some token
    assert not np.array_equal(np.sort(sel, 1),
                              np.sort(np.argsort(-s, axis=1)[:, :K], 1))
    picked = np.take_along_axis(s, np.asarray(sel), 1)
    np.testing.assert_allclose(
        g, picked / (picked.sum(1, keepdims=True) + 1e-6), rtol=1e-5)


@pytest.mark.parametrize("fault", ["weights_from_biased", "no_normalise"])
def test_a_wrong_weighting_disagrees_with_the_reference(fault):
    h, p = _layer(seed=3, bias_std=0.5)
    want = np.asarray(_reference(h, p))
    sizes = dict(SIZES)
    if fault == "no_normalise":
        sizes["norm_topk_prob"] = False
        got = ref.moe_layer(h, p, sizes, ref._mm("f32"))[0]
    else:
        # weigh by s + b: what the layer must not do
        _s, biased, sel = ref.route(h, p, SIZES, ref._mm("f32"))
        g = jnp.take_along_axis(biased, sel, -1)
        g = g / (g.sum(-1, keepdims=True) + 1e-6)
        got = sum(
            jnp.where((sel == e).any(-1, keepdims=True), 1.0, 0.0)
            * jnp.sum(jnp.where(sel == e, g, 0.0), -1, keepdims=True)
            * ref.dense_ffn(h, {k: p[k][e] for k in ("w1", "w3", "w2")},
                            ref._mm("f32")) for e in range(E))
    assert np.abs(np.asarray(got) - want).max() > 1e-2


@pytest.mark.parametrize("impl", IMPLS)
def test_the_four_shares_add_up_to_the_whole_layer(impl):
    """model-configs section 4: a chip that holds a share of the experts
    routes over all of them and computes its own experts' part; the parts
    of a partition add up to the uncut reference's layer."""
    h, p = _layer(seed=4)
    whole = np.asarray(_reference(h, p))
    parts, ref_parts = [], []
    for share in range(4):
        held = (2 * share, 2 * share + 1)
        idx = jnp.asarray(held)
        mine = {**p, **{k: p[k][idx] for k in ("w1", "w3", "w2")}}
        y, sel = _program(h, mine, experts_held=held, impl=impl)
        parts.append(np.asarray(y))
        ref_parts.append(np.asarray(_reference(h, p, experts_held=held)))
        np.testing.assert_allclose(parts[-1], ref_parts[-1], atol=2e-5)
        assert int(sel.max()) > 1 or share == 0     # global ids come back
    np.testing.assert_allclose(sum(parts), whole, atol=5e-5)
    np.testing.assert_allclose(sum(ref_parts), whole, atol=5e-5)


def test_held_weights_must_match_the_experts_held():
    h, p = _layer()
    with pytest.raises(ValueError, match="experts held"):
        _program(h, p, experts_held=(0, 1))

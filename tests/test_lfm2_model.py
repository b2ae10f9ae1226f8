"""`models/lfm2.py` against the plain reference
(benchmark/reference/lfm2_moe.py) on seeded random weights: a small
config with every kind of layer (2 dense convolution layers, then one
period of attention, conv, conv, conv with 8 experts, 2 a token, 4 query
heads a KV head). float32 on the CPU, products at `highest` on both sides
(tests/conftest.py), so the tolerance is that of another summation order:
2e-5 absolute on logits of size ~1."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as ref
from paddle_tpu.models import lfm2


def sizes_of(cfg):
    s = dataclasses.asdict(cfg)
    s.pop("dtype"), s.pop("experts_held")
    s["layer_types"] = list(s["layer_types"])
    return s


@pytest.fixture(scope="module")
def tiny():
    cfg = lfm2.LFM2Config.tiny()
    sizes = sizes_of(cfg)
    return cfg, sizes, ref.make_weights(sizes, 5, jnp.float32)


@pytest.mark.parametrize("grouped", ["dense", "gmm"])
def test_forward_agrees_with_the_reference(tiny, grouped, monkeypatch):
    # the model has no switch for the grouped products: answer for the gate
    from paddle_tpu.parallel import moe
    monkeypatch.setattr(moe, "_auto_grouped", lambda *a: grouped)
    cfg, sizes, params = tiny
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 29)), jnp.int32)
    got = lfm2.forward(params, ids, cfg)
    want = ref.logits(params, ids, sizes)
    assert got.shape == (2, 29, cfg.vocab_size)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_init_params_has_the_reference_layout(tiny):
    cfg, sizes, params = tiny
    import jax
    mine = jax.tree_util.tree_map(lambda a: a.shape,
                                  lfm2.init_params(cfg, 0))
    theirs = jax.tree_util.tree_map(lambda a: a.shape, params)
    assert mine == theirs
    # the experts' bias is drawn non-zero: a program that weighs by it shows
    assert float(jnp.abs(params["layers"][2]["ffn"]["bias"]).max()) > 0.01


def test_published_config_counts():
    cfg = lfm2.LFM2Config()
    assert cfg.layers_of(lfm2.CONV) == 18 and cfg.layers_of(lfm2.ATTN) == 6
    assert cfg.head_dim == 64 and cfg.num_moe_layers == 22
    n = sum(int(np.prod(s)) for l in range(24) for s in _leaves(
        lfm2.layer_shapes(cfg, l))) + 65536 * 2048 + 2048
    assert abs(n / 1e9 - 8.34) < 0.01          # the card's 8.3B


def _leaves(tree):
    for v in tree.values():
        if isinstance(v, dict):
            yield from _leaves(v)
        else:
            yield v


@pytest.mark.parametrize("bad", [
    dict(layer_types=("conv",) * 5),
    dict(layer_types=("conv",) * 5 + ("window",)),
    dict(num_key_value_heads=3)])
def test_config_refuses_what_it_cannot_express(bad):
    with pytest.raises(ValueError):
        lfm2.LFM2Config.tiny(**bad)


def test_conv_state_is_taken_at_the_real_length():
    """A padded prompt leaves the state of its real end: positions
    true_len-2 and true_len-1 of u, zeros before the sequence's start."""
    cfg = lfm2.LFM2Config.tiny()
    p = lfm2.init_params(cfg, 1)["layers"][0]["conv"]
    h = jnp.asarray(np.random.RandomState(2).randn(1, 8, 64), jnp.float32)
    zero = jnp.zeros((1, 2, 64), jnp.float32)
    _y, whole = lfm2.conv_operator(p, h, zero)
    for n in (1, 2, 5):
        _y, st = lfm2.conv_operator(p, h, zero, lengths=jnp.asarray([n]))
        _y, want = lfm2.conv_operator(p, h[:, :n], zero)
        np.testing.assert_array_equal(st, want)
    assert not np.array_equal(whole, want)
    # one token at a time through the state = the whole sequence at once
    y_all, _ = lfm2.conv_operator(p, h, zero)
    st, ys = zero, []
    for t in range(8):
        y, st = lfm2.conv_operator(p, h[:, t:t + 1], st)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), y_all, atol=1e-5)

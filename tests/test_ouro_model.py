"""`models/ouro.py` against the plain reference
(benchmark/reference/ouro_looped.py) on seeded random weights: 3 layers run
4 times a token, 4 heads of 16. float32 on the CPU, products at `highest`
on both sides (tests/conftest.py), so the tolerance is that of another
summation order over 12 layer applications: 2e-5 absolute on logits of
size ~1 (read: 2e-6). Leaving a pass out moves them by ~1."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ouro_looped as ref
from paddle_tpu.models import ouro


def sizes_of(cfg):
    s = dataclasses.asdict(cfg)
    s.pop("dtype")
    return s


@pytest.fixture(scope="module")
def tiny():
    cfg = ouro.OuroConfig.tiny()
    sizes = sizes_of(cfg)
    return cfg, sizes, ref.make_weights(sizes, 5, jnp.float32)


def _ids(cfg, shape=(2, 29), seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, cfg.vocab_size, shape), jnp.int32)


def test_forward_agrees_with_the_reference_at_every_position(tiny):
    cfg, sizes, params = tiny
    ids = _ids(cfg)
    got = ouro.forward(params, ids, cfg)
    want = ref.logits(params, ids, sizes)
    assert got.shape == (2, 29, cfg.vocab_size)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("passes", [1, 3])
def test_a_pass_left_out_is_far_outside_the_tolerance(tiny, passes):
    cfg, sizes, params = tiny
    ids = _ids(cfg)
    got = ouro.forward(params, ids, cfg)
    short = ref.logits(params, ids, sizes, n_passes=passes)
    assert float(jnp.max(jnp.abs(got - short))) > 0.1
    # and the program at that many passes is that reference
    less = dataclasses.replace(cfg, total_ut_steps=passes)
    np.testing.assert_allclose(ouro.forward(params, ids, less), short,
                               atol=2e-5)


def test_init_params_has_the_reference_layout_and_live_gains(tiny):
    cfg, _sizes, params = tiny
    mine = ouro.init_params(cfg, 0)
    shapes = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
    assert shapes(mine) == shapes(params)
    for tree in (mine, params):
        for n in ouro.NORMS:
            g = np.asarray(tree["layers"][n])
            # round one, not at one: a dropped gain shows
            assert 0.05 < g.std() < 0.2 and abs(g.mean() - 1) < 0.05
        assert float(tree["gate_b"]) == 0.0


def test_no_exit_gate_saturates_and_the_exit_mass_sums_to_one(tiny):
    cfg, sizes, params = tiny
    ids = _ids(cfg, (1, 40), seed=3)[0]
    states, lam = ref.passes(params, ids, sizes)
    lam = np.asarray(lam)
    assert lam.shape == (4, 40)
    assert 0.02 < lam.min() and lam.max() < 0.98
    for dist in (ref.exit_distribution, ouro.exit_distribution):
        p = np.asarray(dist(jnp.asarray(lam)))
        np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
        np.testing.assert_allclose(p[0], lam[0], atol=1e-7)
        np.testing.assert_allclose(p[3], np.prod(1 - lam[:3], 0), atol=1e-6)
    # at the published threshold of 1 every token is served the last pass
    _x, first = ref.served_state(states, jnp.asarray(lam), 1.0)
    assert (np.asarray(first) == 3).all()
    # the reference states the rule in general: a low threshold exits early
    _x, first = ref.served_state(states, jnp.asarray(lam), 0.02)
    assert (np.asarray(first) == 0).all()


def test_published_config_counts():
    cfg = ouro.OuroConfig()
    n = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        ouro.param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple)))
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416
    assert n == 48 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert abs(n / 1e9 - 2.668) < 0.001
    assert cfg.cache_rows == 192
    # K and V, every layer of every pass, bf16: 1.5 MiB a token
    assert 2 * cfg.cache_rows * 16 * 128 * 2 == 1.5 * 2 ** 20
    assert int(ouro.cache_row(cfg, 3, 47)) == 191


@pytest.mark.parametrize("bad, error", [
    (dict(early_exit_threshold=0.9), NotImplementedError),
    (dict(num_key_value_heads=3), ValueError),
    (dict(total_ut_steps=0), ValueError)])
def test_config_refuses_what_it_cannot_run(bad, error):
    with pytest.raises(error):
        ouro.OuroConfig.tiny(**bad)

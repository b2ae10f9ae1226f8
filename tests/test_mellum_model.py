"""models/mellum.py against the benchmark's plain reference
(benchmark/reference/mellum_window_moe.py: float32, no kernel, nothing of
the program) on seeded weights at small sizes: the loss and every leaf's
gradient under the program's own routing, YaRN's table against its closed
form, and the four shares' parts of one expert layer adding up to the
uncut reference's whole layer, forward and gradient of the input."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mellum_window_moe as R
from paddle_tpu.models import mellum as M

SIZES = dict(
    vocab_size=256, hidden_size=64, moe_intermediate_size=32,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_experts=8, num_experts_per_tok=2, sliding_window=8,
    rms_norm_eps=1e-6, norm_topk_prob=True, initializer_range=0.1,
    experts_held=3, layer_types=[M.SLIDING] * 3 + [M.FULL],
    rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 16, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}})


def _ids(B=2, T=64, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, SIZES["vocab_size"], (B, T)), jnp.int32)


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_loss_and_gradients_against_the_reference_under_replay(impl):
    cfg = M.MellumConfig.tiny(experts_held=(0, 1, 2), attn_impl=impl)
    params = R.make_weights(SIZES, 5, jnp.float32)
    ids = _ids()
    (loss, chosen), grads = jax.value_and_grad(
        lambda p: M.loss_and_chosen(p, ids, cfg), has_aux=True)(params)
    assert chosen.shape == (4, 2 * 64, 2) and chosen.dtype == jnp.int32
    (want, short), want_grads = jax.value_and_grad(
        lambda p: R.batch_loss(p, ids, SIZES, "f32", chosen, 16),
        has_aux=True)(params)
    assert abs(float(loss) - float(want)) < 1e-5
    # float32 both: the program chose what the reference would have
    assert float(short) < 1e-6
    flat = jax.tree_util.tree_leaves_with_path
    for (path, a), (_, b) in zip(flat(grads), flat(want_grads)):
        gap = float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))
        assert gap < 1e-4, (jax.tree_util.keystr(path), gap)


def test_the_program_and_the_reference_hold_the_same_tree():
    cfg = M.MellumConfig.tiny(experts_held=(0, 1, 2))
    assert M.param_shapes(cfg) == R.weight_shapes(SIZES)
    full = M.MellumConfig(num_hidden_layers=4, vocab_size=24576,
                          experts_held=tuple(range(16)))
    n = sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        M.param_shapes(full), is_leaf=lambda s: isinstance(s, tuple)))
    assert n == 595_154_176         # ISSUE 46's arithmetic
    assert full.layer_types == (M.SLIDING,) * 3 + (M.FULL,)


def test_yarn_table_against_its_closed_form():
    cfg = M.MellumConfig()
    inv, factor = M.rope_table(cfg, M.FULL)
    i = np.arange(64, dtype=np.float64)
    f = 500000.0 ** (-2 * i / 128)
    low = math.floor(128 * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(500000)))
    high = math.ceil(128 * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(500000)))
    assert (low, high) == (18, 35)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(inv, f / 16 * ramp + f * (1 - ramp),
                               rtol=2e-6)
    assert factor == 1.2772588722239782
    # the fast frequencies are kept, the slow ones divided by 16
    np.testing.assert_allclose(inv[:18], f[:18], rtol=2e-6)
    np.testing.assert_allclose(inv[36:], f[36:] / 16, rtol=2e-6)
    plain, one = M.rope_table(cfg, M.SLIDING)
    np.testing.assert_allclose(plain, f, rtol=2e-6)
    assert one == 1.0
    # and the reference computes the same table from the file's group
    ref_inv, ref_factor = R.rope_table(
        {"head_dim": 128, "rope_parameters": {"full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": factor}}}, M.FULL)
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-6)
    assert ref_factor == factor


def test_four_shares_of_an_expert_layer_add_up_to_the_whole():
    """The program's part with experts 0-1, 2-3, 4-5 and 6-7 held, summed,
    is the reference's uncut layer (all 8 held): forward, and the gradient
    of the input."""
    whole = dict(SIZES, experts_held=None)
    p = jax.tree_util.tree_map(
        lambda a: a[1], R.make_weights(whole, 9, jnp.float32)["layers"])
    h = jnp.asarray(np.random.RandomState(3).randn(2, 32, 64), jnp.float32)
    ct = jnp.asarray(np.random.RandomState(4).randn(2, 32, 64), jnp.float32)
    mm = R._mm("f32")

    def ref(h):
        f = jnp.stack([R.moe_layer(h[b], p["ffn"], whole, mm)[0]
                       for b in range(2)])
        return jnp.sum(f * ct), f

    (_, want), want_dh = jax.value_and_grad(ref, has_aux=True)(h)

    def share(held):
        cfg = M.MellumConfig.tiny(experts_held=held)
        idx = jnp.asarray(held)
        ffn = {"wg": p["ffn"]["wg"],
               **{k: p["ffn"][k][idx] for k in ("w1", "w3", "w2")}}

        def f(h):
            y, _sel = M.routed_ffn(ffn, h, cfg)
            return jnp.sum(y * ct), y
        return jax.value_and_grad(f, has_aux=True)(h)

    parts = [share(held) for held in ((0, 1), (2, 3), (4, 5), (6, 7))]
    np.testing.assert_allclose(sum(y for (_, y), _ in parts), want,
                               atol=2e-5)
    np.testing.assert_allclose(sum(dh for _, dh in parts), want_dh,
                               atol=2e-5)
    # and no share is the whole: each leaves something out
    assert all(float(jnp.abs(y - want).max()) > 1e-3 for (_, y), _ in parts)


def test_a_band_is_not_the_triangle_and_yarn_is_not_plain():
    """The two kinds of layer differ in what they compute, not only in
    name: with every layer full the loss moves."""
    params = R.make_weights(SIZES, 6, jnp.float32)
    ids = _ids(seed=1)
    cfg = M.MellumConfig.tiny(experts_held=(0, 1, 2))
    full = M.MellumConfig.tiny(experts_held=(0, 1, 2),
                               layer_types=(M.FULL,) * 4)
    a = float(M.loss_and_chosen(params, ids, cfg)[0])
    b = float(M.loss_and_chosen(params, ids, full)[0])
    assert abs(a - b) > 1e-4


@pytest.mark.parametrize("remat", [True, False])
def test_the_model_trains_through_the_bounded_sorted_arrays(monkeypatch,
                                                            remat):
    """At 2 x 256 tokens a layer has 1,024 pairs and a share of 2 of 8
    experts a bound of 512 rows (`moe.held_rows_bound`): the sorted
    spelling of the grouped products, which the chip takes, then runs its
    bounded arrays inside every rematerialised layer. The loss and every
    leaf's gradient are the dense spelling's (every expert held on every
    row: no sort, no bound), which is what a CPU takes."""
    from paddle_tpu.parallel import moe
    cfg = M.MellumConfig.tiny(experts_held=(0, 1), remat=remat)
    sizes = dict(SIZES, experts_held=2)
    params = R.make_weights(sizes, 7, jnp.float32)
    ids = _ids(2, 256, seed=2)
    assert moe.held_rows_bound(512, 2, 2, 8) == 512 < 1024

    def run(impl):
        monkeypatch.setattr(moe, "_auto_grouped", lambda *a: impl)
        return jax.value_and_grad(
            lambda p: M.loss_and_chosen(p, ids, cfg), has_aux=True)(params)

    (loss, chosen), grads = run("gmm")
    (want, want_chosen), want_grads = run("dense")
    np.testing.assert_array_equal(chosen, want_chosen)
    assert abs(float(loss) - float(want)) < 1e-6
    flat = jax.tree_util.tree_leaves_with_path
    for (path, a), (_, b) in zip(flat(grads), flat(want_grads)):
        gap = float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))
        assert gap < 1e-5, (jax.tree_util.keystr(path), gap)

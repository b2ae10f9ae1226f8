"""`models/deepseek_v3.py` with the three keys `model_type: xing4_0` sets
(`q_lora_rank`, `rope_scaling`, `hc_mult`), each alone and all together,
against the plain reference (benchmark/reference/xing_mhc_mla_moe.py, which
keeps the streams as a real axis [T, 4, C] and the Sinkhorn loop on a [T,
4, 4] array, where the program keeps a tuple of four arrays) on seeded
random weights: one dense layer and two expert layers, 4 heads of 16 + 8
on a latent of 24, hidden 64. float32 on the
CPU, products at `highest` on both sides (tests/conftest.py): 1e-4
absolute on logits of size ~1 (read: 5e-6). And the stream's three steps
(`models/layers.py`) alone."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import xing_mhc_mla_moe as ref
from paddle_tpu.models import deepseek_v3 as ds
from paddle_tpu.models import layers

ATOL = 1e-4
YARN = {"type": "yarn", "factor": 8, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
KEYS = {"q_lora_rank": dict(q_lora_rank=12),
        "rope_scaling": dict(rope_scaling=YARN),
        "hc_mult": dict(hc_mult=4),
        "all": dict(q_lora_rank=12, rope_scaling=YARN, hc_mult=4,
                    n_shared_experts=1)}


def sizes_of(cfg):
    s = dataclasses.asdict(cfg)
    for k in ("dtype", "experts_held"):
        s.pop(k)
    s["rope_scaling"] = cfg.yarn or None
    return s


def tiny(which="all", **kw):
    cfg = ds.DeepseekV3Config.tiny(**KEYS[which], **kw)
    sizes = sizes_of(cfg)
    return cfg, sizes, ref.make_weights(sizes, 5, jnp.float32)


@pytest.mark.parametrize("which", list(KEYS))
def test_forward_agrees_with_the_reference_at_every_position(which):
    cfg, sizes, params = tiny(which)
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (2, 29)), jnp.int32)
    got = ds.forward(params, ids, cfg)
    want = ref.logits(params, ids, sizes)
    assert got.shape == want.shape == (2, 29, cfg.vocab_size)
    assert float(jnp.std(want)) > 0.3           # the layers do something
    assert float(jnp.max(jnp.abs(got - want))) < ATOL
    # the program's own weights have the reference's tree
    like = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
    assert like(ds.init_params(cfg, 3)) == like(params)


def test_each_key_moves_the_logits():
    """A key that did nothing would pass the comparison above on both
    sides: with the same weights, YaRN's table, its mscale^2 and the
    streams' mix each move the logits by far more than the tolerance."""
    cfg, sizes, params = tiny("all")
    ids = jnp.asarray(np.random.RandomState(1).randint(
        0, cfg.vocab_size, (1, 40)), jnp.int32)
    base = ds.forward(params, ids, cfg)
    plain_table = dataclasses.replace(cfg, rope_scaling={
        **YARN, "factor": 1.0000001})     # the table's blend and mscale go
    half_mscale = dataclasses.replace(cfg, rope_scaling={
        **YARN, "mscale": 0.5, "mscale_all_dim": 0.5})
    for other in (plain_table, half_mscale):
        assert float(jnp.max(jnp.abs(
            ds.forward(params, ids, other) - base))) > 100 * ATOL
    assert cfg.softmax_scale == pytest.approx(
        (0.1 * np.log(8) + 1) ** 2 / np.sqrt(24))
    assert half_mscale.softmax_scale == pytest.approx(
        (0.05 * np.log(8) + 1) ** 2 / np.sqrt(24))
    # at the published sizes: 192^-0.5 x 2.0047
    big = ds.DeepseekV3Config(rope_scaling={**YARN, "factor": 64})
    assert big.softmax_scale == pytest.approx(0.07217 * 2.0047, rel=1e-4)
    with pytest.raises(NotImplementedError, match="only YaRN"):
        ds.DeepseekV3Config.tiny(rope_scaling={"type": "linear",
                                               "factor": 2})
    # transformers would scale cos and sin by 0.1 ln(factor) + 1, or by
    # the factor given: neither is built, on either side
    for yarn in ({**YARN, "mscale_all_dim": 0},
                 {k: v for k, v in YARN.items() if k != "mscale"},
                 {**YARN, "attention_factor": 1.2}):
        with pytest.raises(NotImplementedError, match="both set"):
            ds.DeepseekV3Config.tiny(rope_scaling=yarn)


@pytest.mark.parametrize("iters,sums", [(20, True), (1, False)])
def test_the_carry_over_is_doubly_stochastic_after_twenty_iterations(
        iters, sums):
    """H_res's rows sum to 1 within 1e-4 and its columns within 1e-3
    after 20 iterations (at the benchmark's draws, biases of std 1, the
    slowest of these 18 tokens reads 1.2e-4); after one, the rows do and
    the columns are off by over 0.05."""
    cfg, _sizes, params = tiny("hc_mult")
    hc = params["layers"][1]["hc_ffn"]
    X = tuple(jax.random.normal(jax.random.PRNGKey(j),
                                (2, 9, cfg.hidden_size)) for j in range(4))
    pre, post, res = layers.hc_coefficients(hc, X, iters, cfg.hc_eps,
                                            (-30.0, 30.0))
    assert pre.shape == post.shape == (4, 2, 9) and res.shape == (4, 4, 2, 9)
    assert res.dtype == jnp.float32
    assert float(jnp.min(pre)) > 0 and float(jnp.max(pre)) < 1
    assert float(jnp.max(post)) < 2 and float(jnp.std(post)) > 0.1
    rows, cols = jnp.sum(res, axis=1), jnp.sum(res, axis=0)
    assert float(jnp.max(jnp.abs(rows - 1))) < 1e-4
    off = float(jnp.max(jnp.abs(cols - 1)))
    assert off < 1e-3 if sums else off > 0.05, off
    # the reference's loop, on its own layout, makes the same matrix
    mm = ref._mm("f32")
    _pre, _post, want = ref.hyper_coefficients(
        jnp.stack([x[0] for x in X], 1), hc, {**sizes_of(cfg),
                                     "hc_sinkhorn_iters": iters}, mm)
    assert float(jnp.max(jnp.abs(
        jnp.moveaxis(res[:, :, 0], -1, 0) - want))) < 1e-5


def test_read_and_write_are_the_mixes_they_say():
    n, C = 4, 8
    key = jax.random.PRNGKey(3)
    Xs = jax.random.normal(key, (5, n, C))
    X = tuple(Xs[:, j] for j in range(n))
    pre = jax.random.uniform(jax.random.fold_in(key, 1), (n, 5))
    post = jax.random.uniform(jax.random.fold_in(key, 2), (n, 5))
    res = jax.random.uniform(jax.random.fold_in(key, 3), (n, n, 5))
    f = jax.random.normal(jax.random.fold_in(key, 4), (5, C))
    h = layers.hc_read(X, pre)
    assert jnp.allclose(h, jnp.einsum("nt,tnc->tc", pre, Xs), atol=1e-6)
    out = jnp.stack(layers.hc_write(X, res, post, f), 1)
    want = jnp.einsum("ijt,tjc->tic", res, Xs) \
        + post.T[:, :, None] * f[:, None]
    assert jnp.allclose(out, want, atol=1e-6)
    # the identity: carry every stream over, write nothing, read the first
    eye = jnp.broadcast_to(jnp.eye(n)[:, :, None], (n, n, 5))
    assert jnp.array_equal(jnp.stack(layers.hc_write(X, eye, 0 * post, f),
                                     1), Xs)


def test_the_configuration_files_parameter_count_is_init_params_own():
    """benchmark/configs/xing4_29b_a4b_serve.json states 4,792,669,828;
    counted from shapes, nothing is made."""
    from benchmark.runners import serve_mla_hyper
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           "xing4_29b_a4b_serve.json")) as f:
        config = json.load(f)
    cfg = serve_mla_hyper.model_config(config)
    assert (cfg.hc_mult, cfg.q_lora_rank, cfg.hc_sinkhorn_iters) == (4, 768,
                                                                     20)
    shapes = jax.eval_shape(lambda: ds.init_params(cfg, 0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == config["parameters"] == 4_792_669_828
    per_layer = lambda l: sum(int(np.prod(a.shape)) for a in
                              jax.tree_util.tree_leaves(shapes["layers"][l]))
    assert per_layer(0) == 128_196_918 and per_layer(1) == 744_989_046
    hc = shapes["layers"][0]["hc_attn"]
    assert sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(hc)) * 2 == 688_182


def test_the_feed_forward_sub_layer_in_row_blocks_is_the_whole_one(
        monkeypatch):
    """The streams' feed-forward sub-layer runs in equal blocks of at most
    `_FFN_ROW_BLOCK` positions, one after another (no position meets
    another there): one block, four, or five of 7 where 8 does not divide
    the length, give the same logits and the same experts, in the
    positions' order."""
    cfg, _sizes, params = tiny("all")
    ids = jnp.asarray(np.random.RandomState(2).randint(
        0, cfg.vocab_size, (2, 35)), jnp.int32)

    def run():
        x = jnp.take(params["embed"], ids, axis=0)
        pos = jnp.broadcast_to(jnp.arange(35, dtype=jnp.int32), (2, 35))
        attend = lambda p, qn, qr, c, kr, st, l: (ds.expanded_attention(
            p, qn, qr, c, kr, cfg.softmax_scale), st)
        x, _, sel = ds.apply_layers(cfg, params, x, pos, attend, None)
        return x, sel

    whole, sel = run()
    monkeypatch.setattr(ds, "_FFN_ROW_BLOCK", 8)    # 35 = 5 blocks of 7
    blocked, sel_b = run()
    assert sel.shape == sel_b.shape == (2, 70, 2)
    assert jnp.array_equal(sel, sel_b)
    assert float(jnp.max(jnp.abs(whole - blocked))) < 1e-5

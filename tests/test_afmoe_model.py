"""`models/afmoe.py` against the plain reference
(benchmark/reference/afmoe_window_moe.py: every position present, a mask
says what a row attends to) on seeded random weights: two dense layers and
three expert layers (8 routed experts, 2 a token, beside a shared one),
sliding / sliding / sliding / full / sliding with a window of 8, 4 query
heads over 2 KV heads. float32 on the CPU, products at `highest` on both
sides (tests/conftest.py), so the tolerance is that of another summation
order over 5 layers: 5e-5 absolute on logits of size ~1. Any of the
block's particulars left out moves them by 0.1 or more."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import afmoe_window_moe as ref
from paddle_tpu.models import afmoe

ATOL = 5e-5


def sizes_of(cfg):
    s = dataclasses.asdict(cfg)
    for k in ("dtype", "experts_held"):
        s.pop(k)
    s["layer_types"] = list(s["layer_types"])
    return s


@pytest.fixture(scope="module")
def tiny():
    cfg = afmoe.AfmoeConfig.tiny()
    sizes = sizes_of(cfg)
    return cfg, sizes, ref.make_weights(sizes, 5, jnp.float32)


def _ids(cfg, shape=(2, 40), seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, cfg.vocab_size, shape), jnp.int32)


def test_forward_agrees_with_the_reference_at_every_position(tiny):
    cfg, sizes, params = tiny
    ids = _ids(cfg)         # 40 positions: five windows
    got = afmoe.forward(params, ids, cfg)
    want = ref.logits(params, ids, sizes)
    assert got.shape == want.shape == (2, 40, cfg.vocab_size)
    assert float(jnp.std(want)) > 0.3           # the layers do something
    assert float(jnp.max(jnp.abs(got - want))) < ATOL


def test_the_programs_own_weights_have_the_references_tree(tiny):
    cfg, sizes, params = tiny
    own = afmoe.init_params(cfg, 3)
    like = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
    assert like(own) == like(params)
    for tree in (own, params):
        g = tree["layers"][1]["attn"]["q_norm"]
        assert abs(float(jnp.mean(g)) - 1) < 0.2 and float(jnp.std(g)) > 0.02
        assert float(jnp.std(tree["layers"][2]["ffn"]["bias"])) > 0.03


def test_the_published_layer_pattern_is_the_default():
    cfg = afmoe.AfmoeConfig()
    assert cfg.layer_types.count(afmoe.FULL) == 8
    assert all((k == afmoe.FULL) == ((l + 1) % 4 == 0)
               for l, k in enumerate(cfg.layer_types))
    cut = afmoe.AfmoeConfig(num_hidden_layers=6,
                            layer_types=cfg.layer_types[:6])
    assert (cut.layers_of(afmoe.SLIDING), cut.layers_of(afmoe.FULL)) == (5, 1)
    assert [cut.index_in_kind(l) for l in range(6)] == [0, 1, 2, 0, 3, 4]
    assert cut.num_moe_layers == 4 and cut.embed_scale == math.sqrt(2048)


@pytest.mark.parametrize("what", [
    "layer_types=6 kinds for 5 layers", "score_func", "n_group", "heads"])
def test_what_is_not_built_is_refused(what):
    kw = {"layer_types=6 kinds for 5 layers":
          dict(layer_types=(afmoe.SLIDING,) * 6),
          "score_func": dict(score_func="softmax"),
          "n_group": dict(n_group=2), "heads": dict(num_key_value_heads=3)}
    with pytest.raises((ValueError, NotImplementedError)):
        afmoe.AfmoeConfig.tiny(**kw[what])


@pytest.mark.parametrize("T,window,row_block", [
    (64, 8, 16),        # window layers: a block meets window + block keys
    (64, None, 16),     # a full layer in blocks
    (48, 8, 16),        # the first block's keys start before 0: clipped
    (40, 8, 16),        # rows that are not whole blocks: one block
    (24, 16, 16),       # window + block past the sequence: all the keys
])
def test_row_blocks_are_the_dense_mask(T, window, row_block):
    rng = np.random.RandomState(T)
    q = jnp.asarray(rng.randn(2, T, 4, 16), jnp.float32)
    k = jnp.asarray(rng.randn(2, T, 2, 16), jnp.float32)
    v = jnp.asarray(rng.randn(2, T, 2, 16), jnp.float32)
    got = afmoe.banded_causal_attention(q, k, v, 0.25, window, row_block)
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    ok = (j <= i) if window is None else (j <= i) & (i - j < window)
    kk, vv = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * 0.25
    pr = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", pr, vv).reshape(2, T, -1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


FAULTS = ("a window layer attends to the whole context",
          "the full layer is windowed", "the output gate is left out",
          "RoPE is applied on the full layer")


@pytest.mark.parametrize("name", FAULTS)
def test_a_particular_left_out_moves_the_logits(tiny, name, monkeypatch):
    cfg, sizes, params = tiny
    ids = _ids(cfg, seed=1)
    want = ref.logits(params, ids, sizes)
    if name.startswith("the output gate"):
        monkeypatch.setattr(afmoe, "output_gate", lambda p, h: jnp.ones(
            h.shape[:-1] + (p["w_gate"].shape[1],), jnp.float32))
    elif name.startswith("RoPE"):
        monkeypatch.setattr(afmoe, "rotates", lambda cfg, l: True)
    else:
        monkeypatch.setattr(
            afmoe, "window_of",
            (lambda cfg, l: None) if "whole context" in name
            else (lambda cfg, l: cfg.sliding_window))
    got = afmoe.forward(params, ids, cfg)
    assert float(jnp.max(jnp.abs(got - want))) > 0.05


def test_the_shared_and_the_routed_parts_add_up_to_the_references_layer(tiny):
    """The expert layer as the serving cut holds it: the routed parts of
    two shares of the experts and the shared expert, counted once, are the
    reference's whole layer."""
    from paddle_tpu.parallel.moe import dropless_moe_ffn
    cfg, sizes, params = tiny
    p = params["layers"][3]["ffn"]
    h = jnp.asarray(np.random.RandomState(2).randn(24, cfg.hidden_size),
                    jnp.float32)
    want, _ = ref.moe_layer(h, p, sizes, ref._mm("f32"))
    total = 0.0
    for held, shared in (((0, 2, 4, 6), True), ((1, 3, 5, 7), False)):
        idx = jnp.asarray(held)
        sh = p["shared"]
        y, _ = dropless_moe_ffn(
            h, p["wg"], p["bias"], p["w1"][idx], p["w3"][idx], p["w2"][idx],
            top_k=cfg.num_experts_per_tok, norm_topk=cfg.route_norm,
            scale=cfg.route_scale, experts_held=held,
            shared=(sh["w1"], sh["w3"], sh["w2"]) if shared else None)
        total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)

"""`models/deepseek_v3.py` against the plain reference
(benchmark/reference/deepseek_mla_moe.py, the EXPANDED form only) on seeded
random weights: one dense layer and two expert layers (8 routed experts, 2
a token, beside 2 shared), 4 heads of 16 + 8 on a latent of 24. float32 on
the CPU, products at `highest` on both sides (tests/conftest.py), so the
tolerance is that of another summation order over 3 layers: 2e-5 absolute
on logits of size ~1 (read: 1e-6). Leaving the shared experts out moves
them by ~0.3."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_mla_moe as ref
from paddle_tpu.models import deepseek_v3 as ds
from paddle_tpu.parallel.moe import dropless_moe_ffn

ATOL = 2e-5


def sizes_of(cfg):
    s = dataclasses.asdict(cfg)
    for k in ("dtype", "experts_held", "q_lora_rank"):
        s.pop(k)
    return s


@pytest.fixture(scope="module")
def tiny():
    cfg = ds.DeepseekV3Config.tiny()
    sizes = sizes_of(cfg)
    return cfg, sizes, ref.make_weights(sizes, 5, jnp.float32)


def _ids(cfg, shape=(2, 29), seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, cfg.vocab_size, shape), jnp.int32)


def test_forward_agrees_with_the_reference_at_every_position(tiny):
    cfg, sizes, params = tiny
    ids = _ids(cfg)
    got = ds.forward(params, ids, cfg)
    want = ref.logits(params, ids, sizes)
    assert got.shape == want.shape == (2, 29, cfg.vocab_size)
    assert float(jnp.std(want)) > 0.3           # the layers do something
    assert float(jnp.max(jnp.abs(got - want))) < ATOL


def test_the_programs_own_weights_have_the_references_tree(tiny):
    cfg, sizes, params = tiny
    own = ds.init_params(cfg, 3)
    like = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)
    assert like(own) == like(params)
    # gains round one and a bias that matters, on both sides
    for tree in (own, params):
        g = tree["layers"][1]["attn"]["kv_a_layernorm"]
        assert 0.02 < float(jnp.std(g)) < 0.3 and abs(float(jnp.mean(g)) - 1) < 0.2
        assert float(jnp.std(tree["layers"][1]["ffn"]["bias"])) > 0.03


def test_the_absorbed_form_is_the_expanded_form(tiny):
    """One function, two programs: the last position's attention computed
    with the key up-projection folded into the query and the value taken
    from c agrees with the expanded form's (associativity; float32)."""
    cfg, _sizes, params = tiny
    p = params["layers"][1]["attn"]
    rng = np.random.RandomState(1)
    h = jnp.asarray(rng.randn(1, 13, cfg.hidden_size), jnp.float32)
    pos = jnp.arange(13, dtype=jnp.int32)[None]
    q_nope, q_rope, c, kr = ds.latent_projections(p, h, pos, cfg)
    want = ds.expanded_attention(p, q_nope, q_rope, c, kr,
                                 cfg.softmax_scale)[0, -1]
    q = ds.absorb_query(p, q_nope[0, -1], q_rope[0, -1])    # [H, C + dr]
    rows = jnp.concatenate([c, kr], -1)[0]                  # [T, C + dr]
    assert q.shape == (cfg.num_attention_heads, cfg.latent_width)
    pr = jax.nn.softmax(q @ rows.T * cfg.softmax_scale, axis=-1)
    got = ds.expand_value(p, pr @ rows[:, :cfg.kv_lora_rank])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_rope_rotates_adjacent_pairs(tiny):
    cfg, _s, _p = tiny
    x = jnp.asarray(np.random.RandomState(2).randn(1, 5, 3, 8), jnp.float32)
    pos = jnp.asarray([[0, 1, 2, 7, 100]], jnp.int32)
    y = ds.rope_pairs(x, pos, cfg.rope_theta)
    assert jnp.allclose(y[:, 0], x[:, 0])                   # position 0
    # each pair keeps its length; pair i turns by pos x theta^(-2i/d)
    pairs = lambda a: a.reshape(a.shape[:-1] + (4, 2))
    assert jnp.allclose(jnp.sum(pairs(y) ** 2, -1),
                        jnp.sum(pairs(x) ** 2, -1), atol=1e-5)
    ang = 7 * cfg.rope_theta ** (-2 / 8)
    x0, x1 = x[0, 3, 0, 2], x[0, 3, 0, 3]                   # pair 1
    assert jnp.allclose(y[0, 3, 0, 2], x0 * np.cos(ang) - x1 * np.sin(ang),
                        atol=1e-5)
    # the reference's is the same rotation
    assert jnp.allclose(ref._rope(x[0], cfg.rope_theta)[:3], y[0, :3],
                        atol=1e-6)


def test_the_shared_and_the_routed_parts_add_up_to_the_references_layer(tiny):
    """The expert layer in two parts: the routed experts' (dropless, two
    shares of a partition of the 8 experts) and the shared experts', which
    every share would compute alike and which counts once. Their sum is
    the reference's whole layer; the routed part alone is not."""
    cfg, sizes, params = tiny
    p = params["layers"][2]["ffn"]
    h = jnp.asarray(np.random.RandomState(4).randn(17, cfg.hidden_size),
                    jnp.float32)
    mm = ref._mm("f32")
    want, _ = ref.moe_layer(h, p, sizes, mm)
    routed_only, _ = ref.moe_layer(h, p, sizes, mm, shared=False)
    kw = dict(top_k=cfg.num_experts_per_tok, norm_topk=True,
              scale=cfg.routed_scaling_factor)
    shared = (p["shared"]["w1"], p["shared"]["w3"], p["shared"]["w2"])
    parts = []
    for held, sh in (((0, 2, 4, 6), shared), ((1, 3, 5, 7), None)):
        idx = jnp.asarray(held)
        y, sel = dropless_moe_ffn(
            h, p["wg"], p["bias"], p["w1"][idx], p["w3"][idx], p["w2"][idx],
            experts_held=held, shared=sh, **kw)
        parts.append(y)
    assert float(jnp.max(jnp.abs(sum(parts) - want))) < ATOL
    whole, _ = dropless_moe_ffn(h, p["wg"], p["bias"], p["w1"], p["w3"],
                                p["w2"], **kw)
    assert float(jnp.max(jnp.abs(whole - routed_only))) < ATOL
    assert float(jnp.max(jnp.abs(whole - want))) > 1000 * ATOL
    assert sel.shape == (17, cfg.num_experts_per_tok)


def test_what_is_not_built_is_refused():
    with pytest.raises(NotImplementedError, match="only YaRN"):
        ds.DeepseekV3Config.tiny(rope_scaling={"type": "linear",
                                               "factor": 4.0})
    with pytest.raises(NotImplementedError, match="one group"):
        ds.DeepseekV3Config.tiny(n_group=2, topk_group=1)


def test_long_sequences_take_the_query_rows_in_blocks(tiny, monkeypatch):
    """Past two row blocks the plain path never holds [H, T, T] scores: the
    blocked result is the whole one's."""
    cfg, _s, _p = tiny
    monkeypatch.setattr(ds, "_ROW_BLOCK", 8)
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(1, 32, 2, 12), jnp.float32)
    k = jnp.asarray(rng.randn(1, 32, 2, 12), jnp.float32)
    v = jnp.asarray(rng.randn(1, 32, 2, 5), jnp.float32)
    got = ds.causal_attention(q, k, v, 0.3)
    want = ds._causal_rows(q, k, v, 0.3, 0).reshape(1, 32, -1)
    assert got.shape == (1, 32, 10)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-6


def test_the_flash_kernel_takes_a_value_width_of_its_own(monkeypatch):
    """On the chip prefill's expanded attention is the flash kernel with
    192-wide products beside 128-wide values: here 24 beside 16, the
    kernel interpreted, against the plain rows."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, 128, 2, 24), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 2, 24), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, 2, 16), jnp.float32)
    from paddle_tpu.ops import pallas_attention
    called = []
    real = pallas_attention.flash_attention
    monkeypatch.setattr(pallas_attention, "flash_attention",
                        lambda *a, **kw: called.append(1) or real(*a, **kw))
    got = ds.causal_attention(q, k, v, 0.2)
    assert called and got.shape == (1, 128, 32)
    want = ds._causal_rows(q, k, v, 0.2, 0).reshape(1, 128, -1)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5

"""`models/jamba.py` against the plain reference (benchmark/reference/
jamba_ssm.py: float32, the recurrence a `lax.scan` over positions, nothing
of the program) on seeded weights at a small size; the layer order for the
published offsets; what the config refuses. float32 on the CPU with
products at `highest` on both sides; the tolerance on logits of size ~1 is
1e-4 (read: 4e-6)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

from benchmark.reference import jamba_ssm as ref
from paddle_tpu.models import jamba
from paddle_tpu.ops.selective_scan import selective_scan

ATOL = 1e-4
KEYS = ("vocab_size", "hidden_size", "intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "attn_layer_period", "attn_layer_offset", "mamba_d_state",
        "mamba_d_conv", "mamba_expand", "mamba_dt_rank", "rms_norm_eps")


def sizes_of(cfg) -> dict:
    """The reference's sizes of a program config."""
    return {**{k: getattr(cfg, k) for k in KEYS},
            "head_dim": cfg.head_dim, "initializer_range": 0.1}


@pytest.fixture
def small_chunks(monkeypatch):
    """Chunks of 16 positions, so that a test's short sequences cross
    chunks as a prompt bucket crosses the program's 256."""
    monkeypatch.setattr(jamba, "selective_scan",
                        functools.partial(selective_scan, chunk=16))


@pytest.fixture(scope="module")
def tiny():
    cfg = jamba.JambaConfig.tiny()
    sizes = sizes_of(cfg)
    return cfg, sizes, ref.make_weights(sizes, 11, jnp.float32)


def test_the_published_offsets_put_attention_at_layers_7_and_21():
    cfg = jamba.JambaConfig()
    kinds = cfg.layer_types
    assert len(kinds) == 28
    assert [l for l, k in enumerate(kinds) if k == jamba.ATTN] == [7, 21]
    assert cfg.layers_of(jamba.MAMBA) == 26 and cfg.head_dim == 128
    assert cfg.d_inner == 5120
    assert [(k, lo, hi) for k, lo, hi in jamba._runs(kinds)] == [
        (jamba.MAMBA, 0, 7), (jamba.ATTN, 7, 8), (jamba.MAMBA, 8, 21),
        (jamba.ATTN, 21, 22), (jamba.MAMBA, 22, 28)]
    sizes = {**{k: getattr(cfg, k) for k in KEYS}}
    assert [ref.is_attention(sizes, l) for l in range(28)] \
        == [k == jamba.ATTN for k in kinds]


def test_the_published_sizes_are_three_billion_parameters():
    shapes = jamba.weight_shapes(jamba.JambaConfig())
    leaves = jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    n = 0
    for s in leaves:
        size = 1
        for d in s:
            size *= d
        n += size
    assert n == 3_029_337_472
    ours = jax.tree_util.tree_map(
        lambda s: s, shapes, is_leaf=lambda s: isinstance(s, tuple))
    theirs = ref.weight_shapes({**{k: getattr(jamba.JambaConfig(), k)
                                   for k in KEYS}})
    assert ours == theirs


@pytest.mark.parametrize("batch,length", [(1, 1), (2, 5), (2, 40), (1, 33)])
def test_forward_is_the_reference(tiny, small_chunks, batch, length):
    """A length of one takes the one-step update, 5 one short chunk, 40
    two chunks of 16 and a tail, 33 a tail of one."""
    cfg, sizes, params = tiny
    ids = jax.random.randint(jax.random.PRNGKey(length), (batch, length), 0,
                             cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = jamba.forward(params, ids, cfg)
    want = ref.logits(params, ids, sizes)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    assert float(jnp.max(jnp.abs(got - want))) < ATOL


def test_the_state_at_a_length_resumes_the_sequence(tiny, small_chunks):
    """`apply_layers` over a padded bucket with `lengths`, then over the
    rest from the state it left, is `apply_layers` over the whole."""
    cfg, _sizes, params = tiny
    ids = jax.random.randint(jax.random.PRNGKey(5), (1, 24), 0,
                             cfg.vocab_size)
    x = jnp.take(params["embed"], ids, axis=0)
    no_attn = dataclasses.replace(cfg, attn_layer_offset=99)
    attend = None       # every layer a mixer: the state is all there is
    with jax.default_matmul_precision("highest"):
        whole, *_ = jamba.apply_layers(
            no_attn, params, x, *jamba.zero_state(no_attn, 1, x.dtype),
            attend, None)
        padded = jnp.concatenate([x[:, :13], 7.0 * x[:, :11]], axis=1)
        _, ssm, conv, _ = jamba.apply_layers(
            no_attn, params, padded, *jamba.zero_state(no_attn, 1, x.dtype),
            attend, None, lengths=jnp.array([13], jnp.int32))
        rest, *_ = jamba.apply_layers(no_attn, params, x[:, 13:], ssm, conv,
                                      attend, None)
    assert float(jnp.max(jnp.abs(rest - whole[:, 13:]))) < ATOL


@pytest.mark.parametrize("change,error", [
    ({"num_experts": 16}, NotImplementedError),
    ({"tie_word_embeddings": False}, NotImplementedError),
    ({"mamba_proj_bias": True}, NotImplementedError),
    ({"num_attention_heads": 3}, ValueError)])
def test_the_config_refuses_what_is_not_built(change, error):
    with pytest.raises(error):
        jamba.JambaConfig.tiny(**change)


def test_init_params_has_the_tree_and_the_initialisation_that_shows_a_fault():
    cfg = jamba.JambaConfig.tiny()
    p = jamba.init_params(cfg, 3)
    shapes = jax.tree_util.tree_map(lambda a: a.shape, p)
    assert shapes == jamba.weight_shapes(cfg)
    a = jnp.exp(p["mamba"]["A_log"])
    assert jnp.allclose(a[0, :, 0], jnp.arange(1, cfg.mamba_d_state + 1))
    dt = jax.nn.softplus(p["mamba"]["b_dt"])
    assert 1e-3 * 0.99 <= float(dt.min()) and float(dt.max()) <= 1e-1 * 1.01
    for name in ("dt_norm", "b_norm", "c_norm", "D"):
        g = p["mamba"][name]
        assert abs(float(g.mean()) - 1) < 0.2 and float(g.std()) > 0.02

"""LFM2-MoE functional core (LiquidAI LFM2-8B-A1B, `model_type: lfm2_moe`).

A decoder whose layers are of two kinds, listed in `layer_types`:

  * "conv": a gated short convolution. h -> (B, C, X) = h W_in; u = B * X;
    v[t] = sum_k w[:, k] u[t - (K-1) + k] (depthwise, causal, K = 3);
    y = (C * v) W_out. Its state is the last K-1 values of u: per
    SEQUENCE, not per token.
  * "full_attention": grouped-query attention, RMS-normed q and k, RoPE
    (rotate-half over the whole head), causal. Its state is K and V of
    every position.

followed by a feed-forward: a dense SwiGLU in the first `num_dense_layers`
layers, after them `num_experts` routed SwiGLU experts, `num_experts_per_tok`
a token, sigmoid scores, a per-expert bias that chooses and does not weigh
(parallel/moe.py::dropless_moe_ffn). RMSNorm before each sub-layer and
before the head; no biases; the head is tied to the embedding.

Each sub-layer is written ONCE as a pure function of (the layer's weights,
its input, its state) -> (output, new state). `forward` (plain, dense
causal), `prefill` and `decode` (serving/model.py::HybridDecodeModel) are
three drivers over `apply_layers`, which they hand an `attend` function:
what attention does with q, k, v and its state (dense causal; write the
pages then dense causal; write the pages then read them).

Weights: `{"embed" [V, D], "embedding_norm" [D], "layers": [per layer
{"operator_norm" [D], "ffn_norm" [D], "conv": {w_in [D, 3D], w_conv [D, K],
w_out [D, D]} | "attn": {wq [D, Hq d], wk, wv [D, Hkv d], wo [Hq d, D],
q_norm, k_norm [d]}, "ffn": {w1, w3 [D, F], w2 [F, D]} | {wg [D, E],
bias [E], w1, w3 [E, D, Fm], w2 [E, Fm, D]}}]}`. Layers are a list, not a
stack: kinds differ, so the loop over them is unrolled and no layer's
weights are sliced out of a stack on the way to a product.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .layers import (_rope, dense_causal_attention, dense_ffn, rmsnorm,
                     routed_ffn)

__all__ = ["LFM2Config", "init_params", "forward", "apply_layers",
           "conv_operator", "attention_operator", "head_logits"]

CONV, ATTN = "conv", "full_attention"
_PUBLISHED_LAYERS = tuple(
    ATTN if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24))


@dataclass(frozen=True)
class LFM2Config:
    """The published keys of `config.json` (defaults: LFM2-8B-A1B)."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    layer_types: tuple = _PUBLISHED_LAYERS
    num_dense_layers: int = 2
    num_experts: int = 32
    num_experts_per_tok: int = 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    max_position_embeddings: int = 128000
    initializer_range: float = 0.02
    dtype: str = "float32"
    # global ids of the experts whose weights this process holds (None =
    # all): the router still routes over all of them (parallel/moe.py)
    experts_held: tuple | None = None

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.num_hidden_layers} layers")
        bad = set(self.layer_types) - {CONV, ATTN}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def layers_of(self, kind: str) -> int:
        return sum(1 for t in self.layer_types if t == kind)

    @property
    def num_moe_layers(self) -> int:
        return max(0, self.num_hidden_layers - self.num_dense_layers)

    @classmethod
    def tiny(cls, **kw):
        """Every kind of layer at test size: 2 dense conv layers, then one
        period (attention, conv, conv, conv) with 8 experts, 2 a token."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                    moe_intermediate_size=48, num_hidden_layers=6,
                    layer_types=(CONV, CONV, ATTN, CONV, CONV, CONV),
                    num_dense_layers=2, num_experts=8, num_experts_per_tok=2,
                    num_attention_heads=8, num_key_value_heads=2,
                    max_position_embeddings=512)
        base.update(kw)
        return cls(**base)


def layer_shapes(cfg: LFM2Config, l: int) -> dict:
    D, d, K = cfg.hidden_size, cfg.head_dim, cfg.conv_L_cache
    Hq, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    out = {"operator_norm": (D,), "ffn_norm": (D,)}
    if cfg.layer_types[l] == CONV:
        out["conv"] = {"w_in": (D, 3 * D), "w_conv": (D, K),
                       "w_out": (D, D)}
    else:
        out["attn"] = {"wq": (D, Hq * d), "wk": (D, Hkv * d),
                       "wv": (D, Hkv * d), "wo": (Hq * d, D),
                       "q_norm": (d,), "k_norm": (d,)}
    if l < cfg.num_dense_layers:
        F = cfg.intermediate_size
        out["ffn"] = {"w1": (D, F), "w3": (D, F), "w2": (F, D)}
    else:
        E, F = cfg.num_experts, cfg.moe_intermediate_size
        Eh = E if cfg.experts_held is None else len(cfg.experts_held)
        out["ffn"] = {"wg": (D, E), "bias": (E,), "w1": (Eh, D, F),
                      "w3": (Eh, D, F), "w2": (Eh, F, D)}
    return out


def init_params(cfg: LFM2Config, seed: int = 0):
    """Seeded random weights: normals of `initializer_range`, norms at
    one, the experts' bias normal of std 0.1 (non-zero, so that a program
    that weighs by score plus bias shows)."""
    dtype = jnp.dtype(cfg.dtype)
    key = jax.random.PRNGKey(seed)
    std = cfg.initializer_range

    def leaf(path, shape, k):
        name = path[-1].key
        if name.endswith("norm"):
            return jnp.ones(shape, dtype)
        scale = 0.1 if name == "bias" else 0.5 if name == "w_conv" else std
        return (scale * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    def tree(shapes, k):
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda s: isinstance(s, tuple))
        return jax.tree_util.tree_unflatten(treedef, [
            leaf(p, s, jax.random.fold_in(k, i))
            for i, (p, s) in enumerate(flat)])

    top = tree({"embed": (cfg.vocab_size, cfg.hidden_size),
                "embedding_norm": (cfg.hidden_size,)},
               jax.random.fold_in(key, 10_000))
    top["layers"] = [tree(layer_shapes(cfg, l), jax.random.fold_in(key, l))
                     for l in range(cfg.num_hidden_layers)]
    return top


# ---------------------------------------------------------------------------
# sub-layers, each written once
# ---------------------------------------------------------------------------

def conv_operator(p, h, state, lengths=None):
    """The gated short convolution. h [B, T, D]; state [B, K-1, D]: the
    values of u at the K-1 positions before h's first (zeros at a
    sequence's start). Returns (y [B, T, D], new state): u at the K-1
    positions before `lengths` [B] (default T), so that a padded prompt
    leaves the state of its real end."""
    B, T, D = h.shape
    K = p["w_conv"].shape[-1]
    bcx = h @ p["w_in"]
    b, c, x = bcx[..., :D], bcx[..., D:2 * D], bcx[..., 2 * D:]
    u = jnp.concatenate([state.astype(h.dtype), b * x], axis=1)
    w = p["w_conv"].astype(jnp.float32)                       # [D, K]
    v = sum(w[:, k] * u[:, k:k + T].astype(jnp.float32) for k in range(K))
    y = (c * v.astype(h.dtype)) @ p["w_out"]
    if lengths is None:
        new = u[:, T:]
    else:
        idx = lengths[:, None] + jnp.arange(K - 1, dtype=jnp.int32)
        new = jnp.take_along_axis(u, idx[:, :, None], axis=1)
    return y, new.astype(state.dtype)


def attention_operator(p, h, positions, attend, state, cfg):
    """Grouped-query attention. `attend(q, k, v, state) -> (a [B, T,
    Hq d], new state)` is the attention-state interface: what is kept of k
    and v, and what q attends over."""
    B, T, _ = h.shape
    d, Hq, Hkv = cfg.head_dim, cfg.num_attention_heads, \
        cfg.num_key_value_heads
    q = (h @ p["wq"]).reshape(B, T, Hq, d)
    k = (h @ p["wk"]).reshape(B, T, Hkv, d)
    v = (h @ p["wv"]).reshape(B, T, Hkv, d)
    q = _rope(rmsnorm(q, p["q_norm"], cfg.norm_eps), positions,
              cfg.rope_theta)
    k = _rope(rmsnorm(k, p["k_norm"], cfg.norm_eps), positions,
              cfg.rope_theta)
    a, state = attend(q, k, v, state)
    return a @ p["wo"], state


def head_logits(params, x, cfg):
    """Final norm and the tied head, float32 logits."""
    x = rmsnorm(x, params["embedding_norm"], cfg.norm_eps)
    return jnp.einsum("...d,vd->...v", x, params["embed"],
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# the one loop over the layers, and the plain driver
# ---------------------------------------------------------------------------

def apply_layers(cfg, params, x, positions, conv_state, attend, attn_state,
                 lengths=None):
    """x [B, T, D] through every layer.

    conv_state [conv layers, B, K-1, D]; `attend(q, k, v, (attn_state, i))`
    for the i-th attention layer returns (a, attn_state). Returns (x, new
    conv_state, attn_state, sel [expert layers, B T, k])."""
    ci = ai = 0
    new_conv, sels = [], []
    for l, kind in enumerate(cfg.layer_types):
        p = params["layers"][l]
        h = rmsnorm(x, p["operator_norm"], cfg.norm_eps)
        if kind == CONV:
            y, st = conv_operator(p["conv"], h, conv_state[ci], lengths)
            new_conv.append(st)
            ci += 1
        else:
            y, (attn_state, _) = attention_operator(
                p["attn"], h, positions, attend, (attn_state, ai), cfg)
            ai += 1
        x = x + y
        h = rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
        if l < cfg.num_dense_layers:
            f = dense_ffn(p["ffn"], h)
        else:
            f, sel = routed_ffn(p["ffn"], h, cfg)
            sels.append(sel)
        x = x + f
    new_conv = jnp.stack(new_conv) if new_conv else conv_state
    k = cfg.num_experts_per_tok
    sel = jnp.stack(sels) if sels else jnp.zeros(
        (0, x.shape[0] * x.shape[1], k), jnp.int32)
    return x, new_conv, attn_state, sel


def attend_dense(scale):
    """The plain forward's attention state: none."""
    def attend(q, k, v, state):
        return dense_causal_attention(q, k, v, scale), state
    return attend


def zero_conv_state(cfg, batch: int, dtype):
    return jnp.zeros((cfg.layers_of(CONV), batch, cfg.conv_L_cache - 1,
                      cfg.hidden_size), dtype)


def forward(params, ids, cfg: LFM2Config):
    """ids [B, T] -> logits [B, T, V] float32: the whole sequence at once,
    no cache."""
    B, T = ids.shape
    x = jnp.take(params["embed"], ids, axis=0)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x, _, _, _ = apply_layers(
        cfg, params, x, positions, zero_conv_state(cfg, B, x.dtype),
        attend_dense(1.0 / math.sqrt(cfg.head_dim)), None)
    return head_logits(params, x, cfg)

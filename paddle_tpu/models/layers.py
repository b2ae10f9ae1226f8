"""The sub-layers the decoder cores share, each written once: pure functions
of their weights and input, with no state (`routed_ffn` reads the routing
fields of whichever core's config it is handed).

models/lfm2.py, ouro.py, deepseek_v3.py, afmoe.py, jamba.py and mellum.py
each import what they use from here and nothing from one another
(tests/test_model_cores.py). A core that uses a name has it as a module
attribute, which is where the benchmark's fault tools replace it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..parallel.moe import dropless_moe_ffn

__all__ = ["rmsnorm", "rope", "dense_causal_attention", "dense_ffn",
           "routed_ffn", "seeded_tree"]


def rmsnorm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    """Rotate-half RoPE over the whole head at the plain frequencies
    theta^(-2i/d). x [B, T, H, d], positions [B, T]."""
    d = x.shape[-1]
    return rope(x, positions,
                theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))


def rope(x, positions, inv_freq, factor: float = 1.0):
    """Rotate-half RoPE over the whole head from a table of d/2
    frequencies (a scaled kind's: YaRN's blend), cos and sin both times
    `factor` (YaRN's attention factor; a score then carries its square).
    x [B, T, H, d], positions [B, T]."""
    d = x.shape[-1]
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [B, T, d/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return (xf * cos + rot * sin).astype(x.dtype)


def dense_causal_attention(q, k, v, scale, window=None):
    """q [B, T, Hq, d], k and v [B, T, Hkv, d]; KV head j serves query
    heads G j .. G j + G - 1. With `window`, position i attends to j <= i
    with i - j < window. The whole [T, T] of scores at once: short
    sequences (tests, rehearsals) only."""
    B, T, Hq, d = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, T, Hkv, Hq // Hkv, d)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.tril(jnp.ones((T, T), bool))
    if window is not None:
        mask = jnp.logical_and(mask, jnp.triu(jnp.ones((T, T), bool),
                                              1 - window))
    pr = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    a = jnp.einsum("bkgts,bskd->btkgd", pr.astype(v.dtype), v)
    return a.reshape(B, T, Hq * d)


def dense_ffn(p, h):
    return (jax.nn.silu(h @ p["w1"]) * (h @ p["w3"])) @ p["w2"]


def routed_ffn(p, h, cfg):
    """h [B, T, D] -> (f [B, T, D], sel [B T, k] chosen experts). A layer
    with shared experts (`p["shared"]`: models/deepseek_v3.py) gets their
    part added."""
    shape = h.shape
    shared = p.get("shared")
    y, sel = dropless_moe_ffn(
        h.reshape(-1, shape[-1]), p["wg"],
        p["bias"] if cfg.use_expert_bias else None,
        p["w1"], p["w3"], p["w2"], top_k=cfg.num_experts_per_tok,
        norm_topk=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
        experts_held=cfg.experts_held,
        shared=shared and (shared["w1"], shared["w3"], shared["w2"]),
        route=getattr(cfg, "score_func", "sigmoid"))
    return y.reshape(shape), sel


def seeded_tree(shapes, key, std: float, dtype):
    """A tree of seeded random leaves for a tree of shapes: matrices normal
    of `std`; a leaf named `*norm` a gain 1 + 0.1 normal (round one, not AT
    one: a dropped gain then shows); a leaf named `bias` normal of std 0.1
    (a program that weighs by score plus bias, or selects on the score,
    then disagrees). models/afmoe.py draws its weights the same way."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith("norm"):
            leaf = 1.0 + 0.1 * z
        else:
            leaf = (0.1 if name == "bias" else std) * z
        out.append(leaf.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)

"""Ouro functional core (ByteDance Ouro-2.6B, `model_type: ouro`): a looped
decoder. ONE stack of `num_hidden_layers` layers is run `total_ut_steps`
times a token, the same weights every pass; the final norm closes every
pass and its output is the next pass's input.

    x = E[ids]
    for t in range(total_ut_steps):
        for l in range(num_hidden_layers):
            h = rmsnorm(x, input_layernorm[l])
            q, k, v = h Wq[l], h Wk[l], h Wv[l];  q, k = rope(q), rope(k)
            a = attention of pass t (q over the k, v of THIS pass) Wo[l]
            x = x + rmsnorm(a, input_layernorm_2[l])      # sandwich: the
            m = swiglu(rmsnorm(x, post_attention_layernorm[l]))
            x = x + rmsnorm(m, post_attention_layernorm_2[l])   # branch's OUTPUT is normed
        x = rmsnorm(x, norm)
        lam[t] = sigmoid(x . gate_w + gate_b)              # exit gate
    p[t] = lam[t] prod_{j<t} (1 - lam[j]);  p[last] = prod_{j<last} (1 - lam[j])

The state served is x of the first pass at which cumsum(p) reaches
`early_exit_threshold`. At the published 1.0 that is the last pass for
every token, and that is all this core builds: any other threshold raises
`NotImplementedError` (tokens leaving the loop at different passes is
ROADMAP work). A token therefore owns `total_ut_steps x num_hidden_layers`
K/V rows, `cache_row(cfg, t, l) = t L + l`.

The layer is written once (`apply_passes`: a scan over the passes round a
scan over the stacked layers); `forward` (dense causal), and the serving
adapter's prefill and decode (serving/model.py::LoopedDecodeModel) are
drivers that hand it an `attend` function, as models/lfm2.py's are.
RMSNorm, rotate-half RoPE and SwiGLU are models/layers.py's.

Weights: `{"embed" [V, D], "head" [D, V] (untied), "norm" [D], "gate_w"
[D], "gate_b" [], "layers": {the four norms [L, D], "wq" [L, H d, D], "wk",
"wv" [L, Hkv d, D], "wo" [L, H d, D], "ffn": {"w1", "w3" [L, D, F], "w2"
[L, F, D]}}}`: the layers are a stack, scanned. wq, wk and wv lie [out, in]
as a checkpoint's `nn.Linear` holds them: stored [in, out], the TPU's
compiler turned all three stacks round at the top of every serving program
(3 x 403 MB copied and kept, PR 30's compile for a described v5e).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .layers import _rope, dense_causal_attention, dense_ffn, rmsnorm

__all__ = ["OuroConfig", "init_params", "forward", "apply_passes",
           "cache_row", "exit_distribution", "head_logits"]

NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
         "post_attention_layernorm_2")


@dataclass(frozen=True)
class OuroConfig:
    """The published keys of `config.json` (defaults: Ouro-2.6B)."""
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if float(self.early_exit_threshold) != 1.0:
            raise NotImplementedError(
                f"early_exit_threshold {self.early_exit_threshold}: only "
                f"1.0 is built (every token runs every pass); a token "
                f"that leaves the loop early needs the K/V of the passes "
                f"it skipped")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")
        if self.total_ut_steps < 1:
            raise ValueError("total_ut_steps must be at least 1")

    @property
    def cache_rows(self) -> int:
        """K/V rows a token owns: one a layer a pass."""
        return self.total_ut_steps * self.num_hidden_layers

    @classmethod
    def tiny(cls, **kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                    num_hidden_layers=3, num_attention_heads=4,
                    num_key_value_heads=4, head_dim=16,
                    max_position_embeddings=512, initializer_range=0.1)
        base.update(kw)
        return cls(**base)


def cache_row(cfg: OuroConfig, t, l):
    """The K/V row of layer `l` in pass `t`."""
    return t * cfg.num_hidden_layers + l


def param_shapes(cfg: OuroConfig) -> dict:
    D, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    d, Hq, Hkv = cfg.head_dim, cfg.num_attention_heads, \
        cfg.num_key_value_heads
    layers = {n: (L, D) for n in NORMS}
    layers.update(wq=(L, Hq * d, D), wk=(L, Hkv * d, D), wv=(L, Hkv * d, D),
                  wo=(L, Hq * d, D),
                  ffn={"w1": (L, D, F), "w3": (L, D, F), "w2": (L, F, D)})
    return {"embed": (cfg.vocab_size, D), "head": (D, cfg.vocab_size),
            "norm": (D,), "gate_w": (D,), "gate_b": (), "layers": layers}


def init_params(cfg: OuroConfig, seed: int = 0):
    """Seeded random weights: matrices normal of `initializer_range`; the
    norms' gains 1 + 0.1 normal (round one, not AT one: a program that
    drops a gain then shows); the gate's weight normal of 1/sqrt(D) and
    its bias 0, so that x . w is of size one and no lam saturates."""
    dtype = jnp.dtype(cfg.dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    key = jax.random.PRNGKey(seed)
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name == "norm" or name in NORMS:
            leaf = 1.0 + 0.1 * z
        elif name == "gate_w":
            leaf = z / math.sqrt(cfg.hidden_size)
        elif name == "gate_b":
            leaf = jnp.zeros(shape, jnp.float32)
        else:
            leaf = cfg.initializer_range * z
        out.append(leaf.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def apply_passes(cfg: OuroConfig, params, x, positions, attend, state):
    """x [B, T, D] through every layer of every pass.

    `attend(q [B, T, H, d], k, v [B, T, Hkv, d], state, row) -> (a [B, T,
    H d], state)` is the attention-state interface, `row = cache_row(cfg,
    t, l)` a traced scalar: what is kept of this pass's k and v, and what q
    attends over. `state` is carried through both loops (a donated pool
    stays one buffer). Returns (x [B, T, D] of the last pass, after the
    final norm: the state served at threshold 1; lam [passes, B, T]
    float32; state)."""
    B, T, _ = x.shape
    d, Hq, Hkv = cfg.head_dim, cfg.num_attention_heads, \
        cfg.num_key_value_heads
    eps = cfg.rms_norm_eps

    def layer(carry, xs):
        x, state = carry
        p, row = xs
        h = rmsnorm(x, p["input_layernorm"], eps)

        def heads(w, H):        # w [H d, D]: out, in
            return jnp.einsum("btd,ed->bte", h, w).reshape(B, T, H, d)

        q = _rope(heads(p["wq"], Hq), positions, cfg.rope_theta)
        k = _rope(heads(p["wk"], Hkv), positions, cfg.rope_theta)
        v = heads(p["wv"], Hkv)
        a, state = attend(q, k, v, state, row)
        x = x + rmsnorm(a @ p["wo"], p["input_layernorm_2"], eps)
        m = dense_ffn(p["ffn"], rmsnorm(x, p["post_attention_layernorm"],
                                        eps))
        x = x + rmsnorm(m, p["post_attention_layernorm_2"], eps)
        return (x, state), None

    def one_pass(carry, t):
        rows = cache_row(cfg, t, jnp.arange(cfg.num_hidden_layers))
        (x, state), _ = jax.lax.scan(layer, carry, (params["layers"], rows))
        x = rmsnorm(x, params["norm"], eps)
        lam = jax.nn.sigmoid(
            jnp.einsum("btd,d->bt", x.astype(jnp.float32),
                       params["gate_w"].astype(jnp.float32))
            + params["gate_b"].astype(jnp.float32))
        return (x, state), lam

    (x, state), lam = jax.lax.scan(one_pass, (x, state),
                                   jnp.arange(cfg.total_ut_steps))
    return x, lam, state


def exit_distribution(lam):
    """lam [passes, ...] -> p [passes, ...]: the probability of leaving
    the loop after each pass; the last pass takes what is left, so p sums
    to one."""
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)


def head_logits(params, x):
    """The untied head, float32 logits (x is already normed: the final
    norm closes every pass)."""
    return jnp.einsum("...d,dv->...v", x, params["head"],
                      preferred_element_type=jnp.float32)


def forward(params, ids, cfg: OuroConfig):
    """ids [B, T] -> logits [B, T, V] float32: the whole sequence at once,
    no cache, every pass attending over its own k and v."""
    B, T = ids.shape
    x = jnp.take(params["embed"], ids, axis=0)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    scale = 1.0 / math.sqrt(cfg.head_dim)

    def attend(q, k, v, state, row):
        return dense_causal_attention(q, k, v, scale), state

    x, _lam, _ = apply_passes(cfg, params, x, positions, attend, None)
    return head_logits(params, x)

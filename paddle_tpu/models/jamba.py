"""Jamba functional core (AI21-Jamba2-3B, `model_type: jamba`).

A decoder whose layers are of two kinds. Layer l is attention where
l mod `attn_layer_period` == `attn_layer_offset` (7 and 21 of 28), else a
Mamba mixer; every layer then runs a dense SwiGLU MLP (`num_experts` 1:
Jamba2-Mini's experts are not built here, the config raises).

  * "mamba": a = RMSNorm(x); [u | z] = a W_in; c_t = silu(b + sum_j w[j]
    u_{t-K+1+j}) (depthwise, causal, K = 4); [dt | B | C] = c W_x, each
    RMS-normed; delta = softplus(dt W_dt + b_dt); A = -exp(A_log);
    h_t = exp(delta_t (x) A) h_{t-1} + (delta_t c_t) (x) B_t; y_t = h_t C_t
    + D c_t; out = (y silu(z)) W_out. Its state is per SEQUENCE: h [N, E]
    float32 (`ops/selective_scan.py` says why [N, E]) and the last K-1
    values of u.
  * "attention": multi-query, `num_attention_heads` query heads over
    `num_key_value_heads` KV heads, no positions (the recurrence orders the
    tokens), no biases, causal. Its state is K and V of every position.

RMSNorm before each sub-layer and before the head; the head is tied to the
embedding.

Weights are STACKED by kind, so that a run of Mamba layers is one loop over
a layer index (`apply_layers`) and the programs stay small at 28 layers:
`{"embed" [V, D], "final_norm" [D], "layers": {"input_norm", "ff_norm"
[L, D], "mlp": {w1, w3 [L, D, F], w2 [L, F, D]}}, "mamba":
{w_in [Lm, D, 2E], conv_w [Lm, K, E], conv_b [Lm, E], w_x [Lm, E, R + 2N],
dt_norm [Lm, R], b_norm, c_norm [Lm, N], w_dt [Lm, R, E], b_dt [Lm, E],
A_log [Lm, N, E], D [Lm, E], w_out [Lm, E, D]}, "attn": {wq [La, D, H d],
wk, wv [La, D, Hkv d], wo [La, H d, D]}}`.

`forward` (plain, dense causal), `prefill` and `decode`
(serving/model.py::RecurrentDecodeModel) are drivers over `apply_layers`,
which they hand the recurrent state and an `attend` function. A sequence
of ONE position takes the one-step update (`selective_step`), a longer one
the chunked scan.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..ops.selective_scan import StackedRow, selective_scan, selective_step
from .layers import dense_causal_attention, dense_ffn, rmsnorm

__all__ = ["JambaConfig", "init_params", "forward", "apply_layers",
           "mamba_mixer", "zero_state", "head_logits", "MAMBA", "ATTN"]

MAMBA, ATTN = "mamba", "attention"


@dataclass(frozen=True)
class JambaConfig:
    """The published keys of `config.json` (defaults: AI21-Jamba2-3B)."""
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        if self.num_experts > 1:
            raise NotImplementedError(
                f"num_experts = {self.num_experts}: experts beside a "
                f"recurrence are not built; every layer's feed-forward is "
                f"the dense MLP")
        if not self.tie_word_embeddings or self.mamba_proj_bias \
                or not self.mamba_conv_bias:
            raise NotImplementedError(
                "built: a tied head, Mamba projections without bias, a "
                "convolution with one")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.hidden_size % self.num_attention_heads:
            raise ValueError("query heads must divide the hidden size and "
                             "be a multiple of the KV heads")

    @property
    def layer_types(self) -> tuple:
        return tuple(
            ATTN if l % self.attn_layer_period == self.attn_layer_offset
            else MAMBA for l in range(self.num_hidden_layers))

    def layers_of(self, kind: str) -> int:
        return sum(1 for t in self.layer_types if t == kind)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @classmethod
    def tiny(cls, **kw):
        """Both kinds of layer at test size: four layers, the third
        attention."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                    num_hidden_layers=4, num_attention_heads=4,
                    num_key_value_heads=1, attn_layer_period=4,
                    attn_layer_offset=2, mamba_d_state=4, mamba_dt_rank=8,
                    max_position_embeddings=512)
        base.update(kw)
        return cls(**base)


def weight_shapes(cfg: JambaConfig) -> dict:
    D, F, E = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner
    N, R, K = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
    L, Lm, La = cfg.num_hidden_layers, cfg.layers_of(MAMBA), \
        cfg.layers_of(ATTN)
    Hd, Kd = cfg.num_attention_heads * cfg.head_dim, \
        cfg.num_key_value_heads * cfg.head_dim
    return {
        "embed": (cfg.vocab_size, D), "final_norm": (D,),
        "layers": {"input_norm": (L, D), "ff_norm": (L, D),
                   "mlp": {"w1": (L, D, F), "w3": (L, D, F),
                           "w2": (L, F, D)}},
        "mamba": {"w_in": (Lm, D, 2 * E), "conv_w": (Lm, K, E),
                  "conv_b": (Lm, E), "w_x": (Lm, E, R + 2 * N),
                  "dt_norm": (Lm, R), "b_norm": (Lm, N), "c_norm": (Lm, N),
                  "w_dt": (Lm, R, E), "b_dt": (Lm, E), "A_log": (Lm, N, E),
                  "D": (Lm, E), "w_out": (Lm, E, D)},
        "attn": {"wq": (La, D, Hd), "wk": (La, D, Kd), "wv": (La, D, Kd),
                 "wo": (La, Hd, D)}}


def init_params(cfg: JambaConfig, seed: int = 0):
    """Seeded random weights, so that a fault shows: matrices normal of
    `initializer_range`; `A_log` = log(1 .. N) a channel and `b_dt` the
    inverse softplus of steps log-uniform in [1e-3, 1e-1] (Mamba's own: the
    state remembers tens to thousands of tokens, neither forgets at once
    nor never moves); `w_dt` normal of R^-1/2; norm gains and `D` 1 + 0.1
    normal (a dropped gain or skip then disagrees); taps std 0.5, the
    convolution's bias 0.1."""
    dtype = jnp.dtype(cfg.dtype)
    key = jax.random.PRNGKey(seed)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))

    def leaf(name, shape, k):
        normal = lambda std, mean=0.0: mean + std * jax.random.normal(
            k, shape, jnp.float32)
        if name == "A_log":
            a = jnp.log(jnp.arange(1, cfg.mamba_d_state + 1,
                                   dtype=jnp.float32))
            return jnp.broadcast_to(a[None, :, None], shape)
        if name == "b_dt":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if name.endswith("norm") or name == "D":
            return normal(0.1, 1.0)
        return normal({"conv_w": 0.5, "conv_b": 0.1,
                       "w_dt": cfg.mamba_dt_rank ** -0.5}.get(
                           name, cfg.initializer_range))

    return jax.tree_util.tree_unflatten(treedef, [
        leaf(p[-1].key, s, jax.random.fold_in(key, i)).astype(dtype)
        for i, (p, s) in enumerate(flat)])


# ---------------------------------------------------------------------------
# sub-layers, each written once
# ---------------------------------------------------------------------------

def mamba_mixer(p, a, ssm, conv, lengths, cfg):
    """The Mamba mixer of one layer. a [B, T, D] (normed); ssm [B, N, E]
    float32, the state before a's first position (T == 1: or the layer's
    `StackedRow` of the stacked state, and then ssm' is one: the one-step
    update reads the row where it lies); conv [K-1, B, E], u at
    the K-1 positions before it (zeros at a sequence's start; time-major:
    [B, E] are then the minor dimensions, and three taps do not pad to a
    tile of sixteen rows). Returns
    (out [B, T, D], ssm', conv'): the state at `lengths` [B] (default T),
    so that a padded prompt leaves the state of its real end."""
    f32 = jnp.float32
    T = a.shape[1]
    E, N, R, K = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank, \
        cfg.mamba_d_conv
    uz = a @ p["w_in"]
    u, z = uz[..., :E], uz[..., E:]
    # the taps time-major, as the state keeps them: [K-1 + T, B, E]
    taps = jnp.concatenate([conv.astype(u.dtype), jnp.swapaxes(u, 0, 1)],
                           axis=0)
    w = p["conv_w"].astype(f32)
    c = p["conv_b"].astype(f32) + sum(
        w[j] * taps[j:j + T].astype(f32) for j in range(K))
    c = jnp.swapaxes(jax.nn.silu(c), 0, 1).astype(a.dtype)
    if lengths is None:
        conv = taps[T:]
    else:
        at = lengths[None, :] + jnp.arange(K - 1, dtype=jnp.int32)[:, None]
        conv = jnp.take_along_axis(taps, at[:, :, None], axis=0)
    dbc = jnp.einsum("bte,er->btr", c, p["w_x"], preferred_element_type=f32)
    eps = cfg.rms_norm_eps
    dt = rmsnorm(dbc[..., :R], p["dt_norm"], eps)
    Bm = rmsnorm(dbc[..., R:R + N], p["b_norm"], eps)
    Cm = rmsnorm(dbc[..., R + N:], p["c_norm"], eps)
    delta = jax.nn.softplus(
        jnp.einsum("btr,re->bte", dt.astype(a.dtype), p["w_dt"],
                   preferred_element_type=f32) + p["b_dt"].astype(f32))
    A = -jnp.exp(p["A_log"].astype(f32))
    if T == 1:
        y, ssm = selective_step(c[:, 0], delta[:, 0], A, Bm[:, 0], Cm[:, 0],
                                p["D"], ssm)
        y = y[:, None]
    else:
        y, ssm = selective_scan(c, delta, A, Bm, Cm, p["D"], ssm, lengths)
    gated = y.astype(f32) * jax.nn.silu(z.astype(f32))
    return gated.astype(a.dtype) @ p["w_out"], ssm, conv.astype(a.dtype)


def attention_operator(p, a, attend, state, i, cfg):
    """Multi-query attention without positions. `attend(q, k, v, state, i)
    -> (o [B, T, H d], state)` for the i-th attention layer is the
    attention-state interface: what is kept of k and v, what q attends
    over."""
    B, T, _ = a.shape
    d = cfg.head_dim
    q = (a @ p["wq"]).reshape(B, T, cfg.num_attention_heads, d)
    k = (a @ p["wk"]).reshape(B, T, cfg.num_key_value_heads, d)
    v = (a @ p["wv"]).reshape(B, T, cfg.num_key_value_heads, d)
    o, state = attend(q, k, v, state, i)
    return o @ p["wo"], state


def head_logits(params, x, cfg):
    """Final norm and the tied head, float32 logits."""
    x = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum("...d,vd->...v", x, params["embed"],
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# the one loop over the layers, and the plain driver
# ---------------------------------------------------------------------------

def _runs(types):
    """[(kind, first layer, one past the last)] of the maximal runs."""
    out = []
    for l, kind in enumerate(types):
        if out and out[-1][0] == kind:
            out[-1][2] = l + 1
        else:
            out.append([kind, l, l + 1])
    return out


def _at(tree, i):
    """Layer i of a stacked tree; i may be traced."""
    return jax.tree_util.tree_map(
        lambda w: jax.lax.dynamic_index_in_dim(w, i, 0, keepdims=False),
        tree)


def apply_layers(cfg, params, x, ssm, conv, attend, attn_state,
                 lengths=None):
    """x [B, T, D] through every layer. ssm [Mamba layers, B, N, E]
    float32 and conv [Mamba layers, K-1, B, E]: the recurrent state before
    x's first position, a layer's row read and written where it lies (a
    serving cache goes through in place); `attend`: `attention_operator`.
    A run of Mamba layers is ONE loop over the layer index into the
    stacked weights. Returns (x, ssm, conv, attn_state)."""
    eps = cfg.rms_norm_eps

    def mlp(x, lp):
        return x + dense_ffn(lp["mlp"], rmsnorm(x, lp["ff_norm"], eps))

    def mamba_layer(l, carry, shift):
        x, ssm, conv = carry
        m = l - shift
        lp = _at(params["layers"], l)
        # one position: the step takes the stack and the index, so that a
        # decode program reads a layer's state once (ops/selective_scan.py)
        row = StackedRow(ssm, m)
        y, h, cv = mamba_mixer(
            _at(params["mamba"], m), rmsnorm(x, lp["input_norm"], eps),
            row if x.shape[1] == 1 else row.row(), _at(conv, m), lengths,
            cfg)
        if not isinstance(h, StackedRow):
            h = row.put(h)
        return mlp(x + y, lp), h.stack, \
            jax.lax.dynamic_update_index_in_dim(conv, cv, m, 0)

    seen = 0    # attention layers so far
    for kind, lo, hi in _runs(cfg.layer_types):
        if kind == MAMBA:
            x, ssm, conv = jax.lax.fori_loop(
                lo, hi, lambda l, c, s=seen: mamba_layer(l, c, s),
                (x, ssm, conv))
            continue
        for l in range(lo, hi):
            lp = _at(params["layers"], l)
            y, attn_state = attention_operator(
                _at(params["attn"], seen), rmsnorm(x, lp["input_norm"], eps),
                attend, attn_state, seen, cfg)
            x = mlp(x + y, lp)
            seen += 1
    return x, ssm, conv, attn_state


def zero_state(cfg, batch: int, dtype):
    """(ssm, conv) of sequences at their start."""
    Lm = cfg.layers_of(MAMBA)
    return (jnp.zeros((Lm, batch, cfg.mamba_d_state, cfg.d_inner),
                      jnp.float32),
            jnp.zeros((Lm, cfg.mamba_d_conv - 1, batch, cfg.d_inner), dtype))


def forward(params, ids, cfg: JambaConfig):
    """ids [B, T] -> logits [B, T, V] float32: the whole sequence at once,
    no cache."""
    scale = 1.0 / math.sqrt(cfg.head_dim)
    x = jnp.take(params["embed"], ids, axis=0)
    x, _, _, _ = apply_layers(
        cfg, params, x, *zero_state(cfg, ids.shape[0], x.dtype),
        lambda q, k, v, state, i: (dense_causal_attention(q, k, v, scale),
                                   state), None)
    return head_logits(params, x, cfg)

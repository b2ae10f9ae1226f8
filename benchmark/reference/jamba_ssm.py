"""Plain reference of the Jamba decoder (AI21-Jamba2-3B, `config.json` with
`model_type: jamba`; transformers' `modeling_jamba.py` for the equations the
config does not spell out).

    x = E[ids]                                   (no scaling, no positions)
    for l in range(num_hidden_layers):
        a = rmsnorm(x, input_norm[l])
        if l % attn_layer_period == attn_layer_offset:      # layers 7, 21
            q = a Wq (20 heads of 128); k = a Wk, v = a Wv (ONE head of 128)
            x = x + (causal softmax(q k^T / sqrt(128)) v) Wo    (no RoPE)
        else:                                                # Mamba mixer
            [u | z] = a W_in                                  (E = 2 D each)
            c_t = silu(b_conv + sum_j w_conv[j] u_{t-3+j})    (u_{<0} = 0)
            [dt | B | C] = c W_x                              (160 | 16 | 16)
            dt, B, C = rmsnorm(dt), rmsnorm(B), rmsnorm(C)
            delta = softplus(dt W_dt + b_dt);  A = -exp(A_log)
            h_t = exp(delta_t (x) A) h_{t-1} + (delta_t c_t) (x) B_t   (h_{-1} = 0)
            y_t = h_t C_t + D c_t
            x = x + (y silu(z)) W_out
        x = x + (silu(m W1[l]) * (m W3[l])) W2[l],  m = rmsnorm(x, ff_norm[l])
    logits = rmsnorm(x, final_norm) E^T                       (tied head)

Straightforward `jax.numpy` in float32, products at `highest` precision,
the recurrence a plain `lax.scan` over the positions with the state [N, E]
in float32, no cache, no kernels, no batching, nothing imported from the
program. Each layer's weights are cast to float32 as the layer is used, so
a bfloat16 tree costs no float32 copy of the model; attention rows are
taken in blocks, so that a sequence of 4,096 fits beside the weights.

What the config does not state and is this reading of `modeling_jamba.py`
(the configuration's file lists each under `assumed`): which layers are
attention (period and offset as above); the three inner norms; no
positions; head size hidden / heads; no projection biases; the state in
float32; `num_experts` 1 means every layer's feed-forward is the dense
MLP.

Departures from a textbook statement, each for the comparison's sake:

  * weights are random: matrices normal of `initializer_range`; `A_log` =
    log(1 .. N) a channel and `b_dt` the inverse softplus of steps
    log-uniform in [1e-3, 1e-1] (Mamba's own initialisation: a state that
    remembers tens to thousands of tokens); `w_dt` normal of R^-1/2; the
    norms' gains and `D` 1 + 0.1 normal (round one and NOT at one: a
    program that leaves a gain or the skip out then disagrees); taps std
    0.5, the convolution's bias 0.1. Made from the seed on the device, one
    jitted call a leaf, a stacked leaf one layer at a time;
  * `A_log` lies [N, E] and the taps [K, E] (published [E, N] and [E, 1,
    K]): E is the minor dimension of everything the recurrence touches;
  * `precision="fp8"` is the CONTROL, not the reference: every product's
    operands rounded to float8_e4m3fn with a per-tensor scale (the
    recurrence stays float32: its precision is the fault `state_bf16`'s);
  * `precision="bf16"` is a second control, of the configuration's OWN
    precision: every product's operands rounded to bfloat16.

Layout of the weight tree (what both the program and this file read):
`embed [V, D]  final_norm [D]  layers: {input_norm, ff_norm [L, D], mlp:
{w1, w3 [L, D, F], w2 [L, F, D]}}  mamba: {w_in [Lm, D, 2E], conv_w [Lm, K,
E], conv_b [Lm, E], w_x [Lm, E, R + 2N], dt_norm [Lm, R], b_norm, c_norm
[Lm, N], w_dt [Lm, R, E], b_dt [Lm, E], A_log [Lm, N, E], D [Lm, E], w_out
[Lm, E, D]}  attn: {wq [La, D, H d], wk, wv [La, D, d], wo [La, H d, D]}`.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0
ROW_BLOCK = 1024        # attention rows at a time


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)),
                              seed // (2**31 - 1))


def is_attention(sizes: dict, l: int) -> bool:
    return l % sizes["attn_layer_period"] == sizes["attn_layer_offset"]


def weight_shapes(sizes: dict) -> dict:
    D, F = sizes["hidden_size"], sizes["intermediate_size"]
    E = sizes["mamba_expand"] * D
    N, R, K = sizes["mamba_d_state"], sizes["mamba_dt_rank"], \
        sizes["mamba_d_conv"]
    L = sizes["num_hidden_layers"]
    La = sum(is_attention(sizes, l) for l in range(L))
    Lm = L - La
    H, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = D // H
    return {
        "embed": (sizes["vocab_size"], D), "final_norm": (D,),
        "layers": {"input_norm": (L, D), "ff_norm": (L, D),
                   "mlp": {"w1": (L, D, F), "w3": (L, D, F),
                           "w2": (L, F, D)}},
        "mamba": {"w_in": (Lm, D, 2 * E), "conv_w": (Lm, K, E),
                  "conv_b": (Lm, E), "w_x": (Lm, E, R + 2 * N),
                  "dt_norm": (Lm, R), "b_norm": (Lm, N), "c_norm": (Lm, N),
                  "w_dt": (Lm, R, E), "b_dt": (Lm, E), "A_log": (Lm, N, E),
                  "D": (Lm, E), "w_out": (Lm, E, D)},
        "attn": {"wq": (La, D, H * d), "wk": (La, D, Hkv * d),
                 "wv": (La, D, Hkv * d), "wo": (La, H * d, D)}}


@partial(jax.jit, static_argnames=("shape", "kind", "std", "stacked",
                                   "dtype"))
def _leaf(key, shape, kind, std, stacked, dtype):
    def draw(k, s):
        if kind == "a_log":         # [N, E]: log(n + 1) for every channel
            a = jnp.log(jnp.arange(1, s[0] + 1, dtype=jnp.float32))
            return jnp.broadcast_to(a[:, None], s).astype(dtype)
        if kind == "dt_bias":       # inverse softplus of a log-uniform step
            dt = jnp.exp(jax.random.uniform(
                k, s, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        mean = 1.0 if kind == "gain" else 0.0
        return (mean + std * jax.random.normal(k, s, jnp.float32)
                ).astype(dtype)
    if not stacked:
        return draw(key, shape)
    # a stacked leaf: one layer at a time, so that the float32 draw of the
    # whole stack (2.7 GB for the widest) never exists
    return jax.lax.map(lambda l: draw(jax.random.fold_in(key, l), shape[1:]),
                       jnp.arange(shape[0]))


def make_weights(sizes: dict, seed: int, dtype):
    """The weight tree from the seed, on the device, in `dtype`."""
    std = float(sizes.get("initializer_range", 0.02))
    key = seed_key(seed)
    dtype = jnp.dtype(dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(sizes), is_leaf=lambda s: isinstance(s, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        if name == "A_log":
            kind, scale = "a_log", 0.0
        elif name == "b_dt":
            kind, scale = "dt_bias", 0.0
        elif name.endswith("norm") or name == "D":
            kind, scale = "gain", 0.1
        else:
            kind = "normal"
            scale = {"conv_w": 0.5, "conv_b": 0.1,
                     "w_dt": sizes["mamba_dt_rank"] ** -0.5}.get(name, std)
        out.append(_leaf(jax.random.fold_in(key, i), shape, kind, scale,
                         len(path) > 1, dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _quantize(a, dtype, top):
    a = a.astype(jnp.float32)
    scale = top / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    return (a * scale).astype(dtype).astype(jnp.float32) / scale


def _mm(precision: str):
    """The matrix product of this precision, as `mm(spec, a, b)`."""
    hi = jax.lax.Precision.HIGHEST
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(
            spec, a.astype(jnp.float32), b.astype(jnp.float32), precision=hi)
    if precision == "fp8":
        return lambda spec, a, b: jnp.einsum(
            spec, _quantize(a, _F8, _F8_MAX), _quantize(b, _F8, _F8_MAX),
            precision=hi)
    if precision == "bf16":
        return lambda spec, a, b: jnp.einsum(
            spec, a.astype(jnp.bfloat16).astype(jnp.float32),
            b.astype(jnp.bfloat16).astype(jnp.float32), precision=hi)
    raise ValueError(f"unknown precision {precision!r}")


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def attention(a, p, sizes, mm):
    """a [T, D] -> o Wo [T, D]: causal, one KV head for every query head,
    no positions."""
    T, D = a.shape
    H, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = D // H
    q = mm("td,de->te", a, p["wq"]).reshape(T, H, d)
    k = mm("td,de->te", a, p["wk"]).reshape(T, Hkv, d)
    v = mm("td,de->te", a, p["wv"]).reshape(T, Hkv, d)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    out = []
    for r in range(0, T, ROW_BLOCK):
        rows = slice(r, min(T, r + ROW_BLOCK))
        s = mm("qhd,khd->hqk", q[rows], k) / math.sqrt(d)
        qi = jnp.arange(rows.start, rows.stop)[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= qi, s, -1e30)
        out.append(mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v))
    return mm("te,ed->td", jnp.concatenate(out, 0).reshape(T, H * d),
              p["wo"])


def mamba(a, p, sizes, mm):
    """a [T, D] -> the mixer's output [T, D], from a zero state."""
    T, D = a.shape
    E = sizes["mamba_expand"] * D
    N, R, K = sizes["mamba_d_state"], sizes["mamba_dt_rank"], \
        sizes["mamba_d_conv"]
    eps = float(sizes["rms_norm_eps"])
    f32 = jnp.float32
    uz = mm("td,de->te", a, p["w_in"])
    u, z = uz[:, :E], uz[:, E:]
    taps = jnp.concatenate([jnp.zeros((K - 1, E), f32), u], axis=0)
    w = p["conv_w"].astype(f32)
    c = jax.nn.silu(p["conv_b"].astype(f32)
                    + sum(w[j] * taps[j:j + T] for j in range(K)))
    dbc = mm("te,er->tr", c, p["w_x"])
    dt = _rmsnorm(dbc[:, :R], p["dt_norm"], eps)
    B = _rmsnorm(dbc[:, R:R + N], p["b_norm"], eps)
    C = _rmsnorm(dbc[:, R + N:], p["c_norm"], eps)
    delta = jax.nn.softplus(mm("tr,re->te", dt, p["w_dt"])
                            + p["b_dt"].astype(f32))
    A = -jnp.exp(p["A_log"].astype(f32))                        # [N, E]

    def position(h, xs):
        delta_t, c_t, B_t, C_t = xs
        h = jnp.exp(delta_t[None, :] * A) * h \
            + (delta_t * c_t)[None, :] * B_t[:, None]
        return h, jnp.sum(h * C_t[:, None], axis=0)

    _, y = jax.lax.scan(position, jnp.zeros((N, E), f32), (delta, c, B, C))
    y = y + p["D"].astype(f32) * c
    return mm("te,ed->td", y * jax.nn.silu(z), p["w_out"])


def swiglu(m, p, mm):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", m, p["w1"]))
              * mm("td,df->tf", m, p["w3"]), p["w2"])


def hidden_states(params, ids, sizes, precision="f32"):
    """ids [T] -> the final-normed states [T, D]."""
    mm = _mm(precision)
    eps = float(sizes["rms_norm_eps"])
    at = lambda tree, i: jax.tree_util.tree_map(lambda w: w[i], tree)
    x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    seen = 0
    for l in range(sizes["num_hidden_layers"]):
        lp = at(params["layers"], l)
        a = _rmsnorm(x, lp["input_norm"], eps)
        if is_attention(sizes, l):
            x = x + attention(a, at(params["attn"], seen), sizes, mm)
            seen += 1
        else:
            x = x + mamba(a, at(params["mamba"], l - seen), sizes, mm)
        x = x + swiglu(_rmsnorm(x, lp["ff_norm"], eps), lp["mlp"], mm)
    return _rmsnorm(x, params["final_norm"], eps)


def logits(params, ids, sizes, precision="f32"):
    """ids [B, T] -> logits [B, T, V]; one sequence at a time."""
    mm = _mm(precision)
    return jnp.stack([
        mm("td,vd->tv", hidden_states(params, ids[b], sizes, precision),
           params["embed"]) for b in range(ids.shape[0])])


def _sizes_key(sizes):
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, (int, float, bool))))


@partial(jax.jit, static_argnames=("sizes_key", "precision"))
def _gaps(params, ids, alt, sizes_key, precision):
    sizes = dict(sizes_key)
    x = hidden_states(params, ids, sizes, precision)
    lg = _mm(precision)("td,vd->tv", x, params["embed"])[:-1]   # [T-1, V]
    best = jnp.max(lg, axis=-1)
    pick = lambda tok: jnp.take_along_axis(lg, tok[:, None], -1)[:, 0]
    return (best - pick(ids[1:]), best - pick(alt[1:]),
            jnp.argmax(lg, axis=-1).astype(jnp.int32))


def next_token_gaps(params, ids, sizes, precision="f32", alt=None):
    """For one padded sequence ids [T]: at each position t < T-1, how far
    the logit of the token that FOLLOWS (ids[t+1]) lies below the best
    logit, the same for `alt[t+1]`, and the best token. All float32 /
    int32 arrays of length T-1. Padding after the real tokens does not
    reach earlier positions (causal, and the recurrence runs forward)."""
    ids = jnp.asarray(ids, jnp.int32)
    alt = ids if alt is None else jnp.asarray(alt, jnp.int32)
    return _gaps(params, ids, alt, _sizes_key(sizes), precision)

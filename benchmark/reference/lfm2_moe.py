"""Plain reference of the LFM2-MoE decoder (LiquidAI LFM2-8B-A1B,
`config.json` with `model_type: lfm2_moe`; Hugging Face `transformers`'
`Lfm2MoeForCausalLM` for the equations the config does not spell out).

    x = embed[ids]
    for l in layers:
        h = rmsnorm(x, operator_norm[l])
        if layer_types[l] == "conv":
            B, C, X = split3(h @ W_in[l]);  u = B * X
            v[t] = w[:, 0] u[t-2] + w[:, 1] u[t-1] + w[:, 2] u[t]   (u[<0] = 0)
            y = (C * v) @ W_out[l]
        else:
            q, k, v = h @ Wq, h @ Wk, h @ Wv     (32 / 8 / 8 heads of 64)
            q, k = rope(rmsnorm_64(q), rmsnorm_64(k), theta)
            a = causal softmax(q k^T / sqrt(64)) v, KV head j for query
                heads 4j .. 4j+3;  y = a @ Wo
        x = x + y
        h = rmsnorm(x, ffn_norm[l])
        if l < num_dense_layers:  f = (silu(h @ W1) * (h @ W3)) @ W2
        else:
            s = sigmoid(h @ Wg[l]);  sel = top4(s + b[l])
            g = s[sel] / (sum(s[sel]) + 1e-6) * routed_scaling_factor
            f = sum_{e in sel} g_e (silu(h @ W1[l,e]) * (h @ W3[l,e])) @ W2[l,e]
        x = x + f
    logits = rmsnorm(x, embedding_norm) @ embed.T

Straightforward `jax.numpy` in float32, products at `highest` precision,
no cache, no kernels, one loop over the layer list, the experts a plain
loop over e (every expert on every token, then a masked weighted sum), and
nothing imported from the program. Each layer's weights are cast to
float32 as the layer is used, so a bfloat16 tree costs no float32 copy.
Attention rows are taken in blocks so that the scores of 4096 positions
fit beside the weights. What the config does not state, and is taken from
`transformers` (the configuration's file lists these under `assumed`): the
head is tied to the embedding; a head is 64 = 2048 / 32; the in-projection's
thirds are B, C, x in that order; RoPE is the rotate-half form over the
whole head; 1e-6 in the weights' normalisation; router scores in float32.

Departures from a textbook statement, each for the comparison's sake:

  * weights are random normals of std 0.02, the convolution's taps of
    std 0.5 and the experts' bias of std 0.1 (NOT zero: a program that
    weighs by s + b, or selects on s, then disagrees), norms at one, made
    from the seed on the device, one jitted call a layer;
  * `precision="fp8"` is the CONTROL, not the reference: every product's
    operands rounded to float8_e4m3fn with a per-tensor scale;
  * `experts_held`: only those experts' terms of f are summed (the chip's
    share of an expert-parallel layer; model-configs section 4);
  * `routing` (replay): the experts of each position are GIVEN (what the
    program chose), the weights still come from the reference's own
    scores. Top-4 of 32 is discontinuous, so a bfloat16 program and this
    float32 reference now and then choose differently at a near tie, and
    every later position inherits the difference; under replay what is
    left between them is precision alone. `replay()` also returns how far
    each given expert's biased score lies below the reference's own 4th
    best (0 where the program chose as the reference would): a router that
    selects on the wrong quantity shows there.

Layout of the weight tree (what both the program and this file read):
`embed [V, D]  embedding_norm [D]  layers: list of {operator_norm [D],
ffn_norm [D], conv {w_in [D, 3D], w_conv [D, K], w_out [D, D]} | attn {wq
[D, Hq d], wk, wv [D, Hkv d], wo [Hq d, D], q_norm, k_norm [d]}, ffn {w1,
w3 [D, F], w2 [F, D]} | {wg [D, E], bias [E], w1, w3 [E, D, Fm], w2 [E,
Fm, D]}}`.
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


def _head_dim(sizes):
    return int(sizes.get("head_dim",
                         sizes["hidden_size"] // sizes["num_attention_heads"]))


def layer_shapes(sizes: dict, l: int) -> dict:
    D, K = sizes["hidden_size"], sizes["conv_L_cache"]
    d = _head_dim(sizes)
    Hq, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    out = {"operator_norm": (D,), "ffn_norm": (D,)}
    if sizes["layer_types"][l] == "conv":
        out["conv"] = {"w_in": (D, 3 * D), "w_conv": (D, K), "w_out": (D, D)}
    else:
        out["attn"] = {"wq": (D, Hq * d), "wk": (D, Hkv * d),
                       "wv": (D, Hkv * d), "wo": (Hq * d, D),
                       "q_norm": (d,), "k_norm": (d,)}
    if l < sizes["num_dense_layers"]:
        F = sizes["intermediate_size"]
        out["ffn"] = {"w1": (D, F), "w3": (D, F), "w2": (F, D)}
    else:
        E, F = sizes["num_experts"], sizes["moe_intermediate_size"]
        out["ffn"] = {"wg": (D, E), "bias": (E,), "w1": (E, D, F),
                      "w3": (E, D, F), "w2": (E, F, D)}
    return out


def weight_shapes(sizes: dict) -> dict:
    return {"embed": (sizes["vocab_size"], sizes["hidden_size"]),
            "embedding_norm": (sizes["hidden_size"],),
            "layers": [layer_shapes(sizes, l)
                       for l in range(sizes["num_hidden_layers"])]}


def _is_shape(s):
    return isinstance(s, tuple)


@partial(jax.jit, static_argnames=("shapes_key", "std", "dtype"))
def _make_tree(key, shapes_key, std, dtype):
    shapes = jax.tree_util.tree_unflatten(*shapes_key)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)
    out = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        if name.endswith("norm"):
            leaf = jnp.ones(shape, jnp.float32)
        else:
            scale = {"bias": 0.1, "w_conv": 0.5}.get(name, std)
            leaf = scale * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        out.append(leaf.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _tree_key(shapes):
    flat, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_shape)
    return treedef, tuple(flat)


def make_weights(sizes: dict, seed: int, dtype):
    """The weight tree from the seed, on the device, in `dtype`: one
    jitted call for the embedding and one a layer (layers of one shape
    share a program), so that no call holds more than a layer in
    float32."""
    std = float(sizes.get("initializer_range", 0.02))
    key = seed_key(seed)
    dtype = jnp.dtype(dtype)
    shapes = weight_shapes(sizes)
    layers = shapes.pop("layers")
    top = _make_tree(jax.random.fold_in(key, 10_000), _tree_key(shapes),
                     std, dtype)
    top["layers"] = [
        _make_tree(jax.random.fold_in(key, l), _tree_key(s), std, dtype)
        for l, s in enumerate(layers)]
    return top


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
    raise ValueError(f"unknown precision {precision!r}")


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """x [T, H, d], positions 0 .. T-1, rotate-half over the whole head."""
    T, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def conv_layer(h, p, mm):
    """h [T, D] -> y [T, D]."""
    T, D = h.shape
    K = p["w_conv"].shape[-1]
    bcx = mm("td,de->te", h, p["w_in"])
    b, c, x = bcx[:, :D], bcx[:, D:2 * D], bcx[:, 2 * D:]
    u = jnp.concatenate([jnp.zeros((K - 1, D), jnp.float32), b * x], 0)
    w = p["w_conv"].astype(jnp.float32)
    v = sum(w[:, k] * u[k:k + T] for k in range(K))
    return mm("td,de->te", c * v, p["w_out"])


def attention_layer(h, p, sizes, mm):
    T, _ = h.shape
    d = _head_dim(sizes)
    Hq, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    G = Hq // Hkv
    eps, theta = float(sizes["norm_eps"]), float(sizes["rope_theta"])
    q = mm("td,de->te", h, p["wq"]).reshape(T, Hq, d)
    k = mm("td,de->te", h, p["wk"]).reshape(T, Hkv, d)
    v = mm("td,de->te", h, p["wv"]).reshape(T, Hkv, d)
    q = _rope(_rmsnorm(q, p["q_norm"], eps), theta)
    k = _rope(_rmsnorm(k, p["k_norm"], eps), theta)
    # query head h reads KV head h // G
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    out = []
    for r in range(0, T, ROW_BLOCK):
        rows = slice(r, min(T, r + ROW_BLOCK))
        s = mm("qhd,khd->hqk", q[rows], k) / math.sqrt(d)
        qi = jnp.arange(rows.start, rows.stop)[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= qi, s, -1e30)
        out.append(mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v))
    a = jnp.concatenate(out, 0).reshape(T, Hq * d)
    return mm("te,ed->td", a, p["wo"])


def dense_ffn(h, p, mm):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", h, p["w1"]))
              * mm("td,df->tf", h, p["w3"]), p["w2"])


def route(h, p, sizes, mm):
    """(s [T, E] scores, biased [T, E] what chooses, sel [T, k] own
    choice)."""
    s = jax.nn.sigmoid(mm("td,de->te", h, p["wg"]))
    biased = s + p["bias"].astype(jnp.float32) \
        if sizes.get("use_expert_bias", True) else s
    _, sel = jax.lax.top_k(biased, int(sizes["num_experts_per_tok"]))
    return s, biased, sel


def moe_layer(h, p, sizes, mm, experts_held=None, given=None):
    """h [T, D] -> (f [T, D], shortfall [T]). `given` [T, k] int: the
    experts to use (-1 in a row's first place = this row chooses its own);
    `experts_held`: only these experts' terms are summed."""
    E = int(sizes["num_experts"])
    s, biased, sel = route(h, p, sizes, mm)
    short = jnp.zeros(h.shape[:1], jnp.float32)
    if given is not None:
        own = given[:, :1] < 0
        given = jnp.where(own, sel, given)
        kth = jnp.min(jnp.take_along_axis(biased, sel, -1), -1)
        got = jnp.min(jnp.take_along_axis(biased, given, -1), -1)
        short = kth - got
        sel = given
    g = jnp.take_along_axis(s, sel, -1)
    if sizes.get("norm_topk_prob", True):
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-6)
    g = g * float(sizes.get("routed_scaling_factor", 1.0))
    # weight of expert e for token t: its g where chosen, else 0
    w = jnp.sum(jnp.where(sel[:, :, None] == jnp.arange(E), g[:, :, None],
                          0.0), axis=1)                          # [T, E]
    if experts_held is not None:
        held = jnp.zeros((E,), bool).at[jnp.asarray(experts_held)].set(True)
        w = jnp.where(held[None, :], w, 0.0)

    def one(f, xs):
        w1, w3, w2, we = xs
        y = mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", h, w1))
               * mm("td,df->tf", h, w3), w2)
        return f + we[:, None] * y, None

    f, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (p["w1"], p["w3"], p["w2"], w.T))
    return f, short


def hidden_states(params, ids, sizes, precision="f32", experts_held=None,
                  routing=None):
    """ids [T] -> (final-norm output [T, D] float32, shortfall [T, expert
    layers]). `routing` [T, expert layers, k] as in `moe_layer`."""
    mm = _mm(precision)
    eps = float(sizes["norm_eps"])
    x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    shorts, m = [], 0
    for l, kind in enumerate(sizes["layer_types"]):
        p = params["layers"][l]
        h = _rmsnorm(x, p["operator_norm"], eps)
        if kind == "conv":
            x = x + conv_layer(h, p["conv"], mm)
        else:
            x = x + attention_layer(h, p["attn"], sizes, mm)
        h = _rmsnorm(x, p["ffn_norm"], eps)
        if l < sizes["num_dense_layers"]:
            x = x + dense_ffn(h, p["ffn"], mm)
        else:
            f, short = moe_layer(
                h, p["ffn"], sizes, mm, experts_held,
                None if routing is None else routing[:, m].astype(jnp.int32))
            x = x + f
            shorts.append(short)
            m += 1
    short = jnp.stack(shorts, 1) if shorts else jnp.zeros((ids.shape[0], 0))
    return _rmsnorm(x, params["embedding_norm"], eps), short


def logits(params, ids, sizes, precision="f32", experts_held=None,
           routing=None):
    """ids [B, T] -> logits [B, T, V]; one sequence at a time."""
    rows = []
    for b in range(ids.shape[0]):
        x, _ = hidden_states(params, ids[b], sizes, precision, experts_held,
                             None if routing is None else routing[b])
        rows.append(_mm(precision)("td,vd->tv", x, params["embed"]))
    return jnp.stack(rows)


def _sizes_key(sizes):
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in sizes.items()
        if isinstance(v, (int, float, bool, list, tuple))))


@partial(jax.jit, static_argnames=("sizes_key", "precision"))
def _replay(params, ids, alt, routing, sizes_key, precision):
    sizes = dict(sizes_key)
    x, short = hidden_states(params, ids, sizes, precision, None, routing)
    lg = _mm(precision)("td,vd->tv", x, params["embed"])[:-1]    # [T-1, V]
    best = jnp.max(lg, axis=-1)
    pick = lambda tok: jnp.take_along_axis(lg, tok[:, None], -1)[:, 0]
    return (best - pick(ids[1:]), best - pick(alt[1:]),
            jnp.argmax(lg, axis=-1).astype(jnp.int32), short)


def replay(params, ids, sizes, precision="f32", alt=None, routing=None):
    """`next_token_gaps` and, as a fourth array, the shortfall [T, expert
    layers] of the GIVEN experts: how far the least of a position's given
    experts lies, in biased score, below the reference's own k-th best (0
    where it would have chosen the same set). `routing` [n <= T, expert
    layers, k]: positions past n choose their own."""
    ids = jnp.asarray(ids, jnp.int32)
    alt = ids if alt is None else jnp.asarray(alt, jnp.int32)
    if routing is not None:
        import numpy as np
        routing = np.asarray(routing)
        full = np.full((ids.shape[0],) + routing.shape[1:], -1, np.int32)
        full[:routing.shape[0]] = routing
        routing = jnp.asarray(full)
    return _replay(params, ids, alt, routing, _sizes_key(sizes), precision)


def next_token_gaps(params, ids, sizes, precision="f32", alt=None,
                    routing=None):
    """For one padded sequence ids [T]: at each position t < T-1, how far
    the logit of the token that FOLLOWS (ids[t+1]) lies below the best
    logit, the same for `alt[t+1]`, and the best token. All float32 /
    int32 arrays of length T-1. Padding after the real tokens does not
    reach earlier positions (causal)."""
    return replay(params, ids, sizes, precision, alt, routing)[:3]

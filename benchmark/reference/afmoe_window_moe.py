"""Plain reference of the AFMoE block as Arcee's Trinity-Mini configures it
(`config.json` with `model_type: afmoe`; Hugging Face `transformers`'
`modeling_afmoe.py` for the equations the config does not spell out, read
from memory: no network here, so each is listed under `assumed` in the
configuration's file).

    x = E[ids] * sqrt(D)                                    (mup_enabled)
    for l in layers:
        a = rmsnorm(x, input_layernorm[l])
        q = a @ Wq -> [T, 32, 128];  k = a @ Wk, v = a @ Wv -> [T, 4, 128];  g = a @ Wg -> [T, 4096]
        q = rmsnorm(q, q_norm), k = rmsnorm(k, k_norm)      over the 128 of a head
        if layer_types[l] == "sliding_attention":  q, k = rope(q), rope(k)   (rotate-half, theta 1e4)
        o_i = sum_j softmax_j(q_i . k_j / sqrt(128)) v_j    over j <= i, and on a sliding
              layer i - j < sliding_window; KV head h serves query heads 8h .. 8h+7
        x = x + rmsnorm((o * sigmoid(g)) @ Wo, post_attention_layernorm[l])
        m = rmsnorm(x, pre_mlp_layernorm[l])
        if l < num_dense_layers:  f = (silu(m @ W1) * (m @ W3)) @ W2
        else:
            s = sigmoid(m @ Wr);  sel = top8(s + b[l])
            w = route_scale * s[sel] / (sum(s[sel]) + 1e-20)
            f = sum_{e in sel} w_e swiglu_e(m) + swiglu_shared(m)
        x = x + rmsnorm(f, post_mlp_layernorm[l])
    logits = rmsnorm(x, norm) @ W_out

NO cache, no ring, no kernel, no batching, and nothing is imported from
the program: every layer sees every position and a mask says what a row
attends to. Straightforward `jax.numpy` in float32, products at `highest`
precision, one loop over the layer list, the experts a plain loop over e
(every expert on every token, then a masked weighted sum). Each layer's
weights are cast to float32 as the layer is used; attention rows and the
head's rows are taken in blocks so that 18,432 positions fit beside 8.6 GB
of weights.

Departures from a textbook statement, each for the comparison's sake (as
reference/deepseek_mla_moe.py's): weights random normals of std 0.02, the
experts' bias of std 0.1 (NOT zero: a program that weighs by s + b, or
selects on s, then disagrees), the norms' gains 1 + 0.1 normal (a dropped
gain shows), made from the seed on the device, one jitted call a layer;
`precision="fp8"` is the CONTROL, not the reference; `routing` (replay):
the experts of each position are GIVEN (what the program chose), the
weights still come from the reference's own scores, and `replay()` also
returns how far each given expert's biased score lies below the
reference's own 8th best (reference/lfm2_moe.py says why).

Layout of the weight tree (what both the program and this file read):
`embed [V, D]  head [D, V]  norm [D]  layers: list of {input_layernorm,
post_attention_layernorm, pre_mlp_layernorm, post_mlp_layernorm [D], attn
{wq, w_gate [D, H d], wk, wv [D, Hkv d], wo [H d, D], q_norm, k_norm [d]},
ffn {w1, w3 [D, F], w2 [F, D]} | {wg [D, E], bias [E], w1, w3 [E, D, Fm],
w2 [E, Fm, D], shared {w1, w3 [D, n Fm], w2 [n Fm, D]}}}`.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0
ROW_BLOCK = 256         # attention rows, and rows of the head, at a time
SLIDING = "sliding_attention"


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)),
                              seed // (2**31 - 1))


def layer_shapes(sizes: dict, l: int) -> dict:
    D, d = sizes["hidden_size"], sizes["head_dim"]
    H, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    out = {"input_layernorm": (D,), "post_attention_layernorm": (D,),
           "pre_mlp_layernorm": (D,), "post_mlp_layernorm": (D,),
           "attn": {"wq": (D, H * d), "wk": (D, Hkv * d), "wv": (D, Hkv * d),
                    "w_gate": (D, H * d), "wo": (H * d, D),
                    "q_norm": (d,), "k_norm": (d,)}}
    if l < sizes["num_dense_layers"]:
        F = sizes["intermediate_size"]
        out["ffn"] = {"w1": (D, F), "w3": (D, F), "w2": (F, D)}
    else:
        E, F = sizes["num_experts"], sizes["moe_intermediate_size"]
        Fs = sizes["num_shared_experts"] * F
        out["ffn"] = {"wg": (D, E), "bias": (E,), "w1": (E, D, F),
                      "w3": (E, D, F), "w2": (E, F, D),
                      "shared": {"w1": (D, Fs), "w3": (D, Fs),
                                 "w2": (Fs, D)}}
    return out


def weight_shapes(sizes: dict) -> dict:
    D, V = sizes["hidden_size"], sizes["vocab_size"]
    return {"embed": (V, D), "head": (D, V), "norm": (D,),
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
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith("norm"):
            leaf = 1.0 + 0.1 * z
        else:
            leaf = (0.1 if name == "bias" else std) * z
        out.append(leaf.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _tree_key(shapes):
    flat, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_shape)
    return treedef, tuple(flat)


def make_weights(sizes: dict, seed: int, dtype):
    """The weight tree from the seed, on the device, in `dtype`: one
    jitted call for the embedding and the head and one a layer (layers of
    one shape share a program), so that no call holds more than a layer in
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
    """x [T, heads, d], positions 0 .. T-1, rotate-half: lane i of the
    first half pairs with lane i of the second, angle position x
    theta^(-2i/d)."""
    T, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_layer(h, p, sizes, mm, kind):
    """h [T, D] -> y [T, D]: grouped-query attention of layer kind `kind`,
    every position present, the output gated."""
    T, _ = h.shape
    H, Hkv, d = sizes["num_attention_heads"], sizes["num_key_value_heads"], \
        sizes["head_dim"]
    eps = float(sizes["rms_norm_eps"])
    q = _rmsnorm(mm("td,de->te", h, p["wq"]).reshape(T, H, d), p["q_norm"],
                 eps)
    k = _rmsnorm(mm("td,de->te", h, p["wk"]).reshape(T, Hkv, d),
                 p["k_norm"], eps)
    v = mm("td,de->te", h, p["wv"]).reshape(T, Hkv, d)
    window = None
    if kind == SLIDING:
        theta = float(sizes["rope_theta"])
        q, k = _rope(q, theta), _rope(k, theta)
        window = int(sizes["sliding_window"])
    # every query head its own key and value: KV head h serves 8h .. 8h+7
    k, v = jnp.repeat(k, H // Hkv, axis=1), jnp.repeat(v, H // Hkv, axis=1)
    block = min(ROW_BLOCK, T)
    if T % block:
        raise ValueError(f"{T} positions are not whole blocks of {block}")

    def rows(r):
        at = r * block
        s = mm("qhd,khd->hqk", jax.lax.dynamic_slice_in_dim(q, at, block, 0),
               k) / math.sqrt(d)
        qi = at + jnp.arange(block)[:, None]
        kj = jnp.arange(T)[None, :]
        ok = kj <= qi
        if window is not None:
            ok = ok & (qi - kj < window)
        return mm("hqk,khd->qhd", jax.nn.softmax(jnp.where(ok, s, -1e30),
                                                 axis=-1), v)

    o = jax.lax.map(rows, jnp.arange(T // block)).reshape(T, -1)
    gate = jax.nn.sigmoid(mm("td,de->te", h, p["w_gate"]))
    return mm("te,ed->td", o * gate, p["wo"])


def swiglu(h, p, mm):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", h, p["w1"]))
              * mm("td,df->tf", h, p["w3"]), p["w2"])


def route(h, p, sizes, mm):
    """(s [T, E] scores, biased [T, E] what chooses, sel [T, k] own
    choice)."""
    s = jax.nn.sigmoid(mm("td,de->te", h, p["wg"]))
    biased = s + p["bias"].astype(jnp.float32)
    _, sel = jax.lax.top_k(biased, int(sizes["num_experts_per_tok"]))
    return s, biased, sel


def moe_layer(h, p, sizes, mm, given=None, shared=True):
    """h [T, D] -> (f [T, D], shortfall [T]). `given` [T, k] int: the
    experts to use (-1 in a row's first place = this row chooses its own).
    `shared=False` leaves the shared expert's term out (tests add the two
    parts up)."""
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
    if sizes.get("route_norm", True):
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    g = g * float(sizes.get("route_scale", 1.0))
    # weight of expert e for token t: its g where chosen, else 0
    w = jnp.sum(jnp.where(sel[:, :, None] == jnp.arange(E), g[:, :, None],
                          0.0), axis=1)                          # [T, E]

    def one(f, xs):
        w1, w3, w2, we = xs
        y = swiglu(h, {"w1": w1, "w3": w3, "w2": w2}, mm)
        return f + we[:, None] * y, None

    f, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (p["w1"], p["w3"], p["w2"], w.T))
    if shared:
        f = f + swiglu(h, p["shared"], mm)
    return f, short


def hidden_states(params, ids, sizes, precision="f32", routing=None):
    """ids [T] -> (final-norm output [T, D] float32, shortfall [T, expert
    layers]). `routing` [T, expert layers, k] as in `moe_layer`."""
    mm = _mm(precision)
    eps = float(sizes["rms_norm_eps"])
    x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    if sizes.get("mup_enabled", True):
        x = x * math.sqrt(sizes["hidden_size"])
    shorts, m = [], 0
    for l, p in enumerate(params["layers"]):
        h = _rmsnorm(x, p["input_layernorm"], eps)
        a = attention_layer(h, p["attn"], sizes, mm, sizes["layer_types"][l])
        x = x + _rmsnorm(a, p["post_attention_layernorm"], eps)
        h = _rmsnorm(x, p["pre_mlp_layernorm"], eps)
        if l < sizes["num_dense_layers"]:
            f = swiglu(h, p["ffn"], mm)
        else:
            f, short = moe_layer(
                h, p["ffn"], sizes, mm,
                None if routing is None else routing[:, m].astype(jnp.int32))
            shorts.append(short)
            m += 1
        x = x + _rmsnorm(f, p["post_mlp_layernorm"], eps)
    short = jnp.stack(shorts, 1) if shorts else jnp.zeros((ids.shape[0], 0))
    return _rmsnorm(x, params["norm"], eps), short


def logits(params, ids, sizes, precision="f32", routing=None):
    """ids [B, T] -> logits [B, T, V]; one sequence at a time (tests: the
    whole [T, V] at once)."""
    rows = []
    for b in range(ids.shape[0]):
        x, _ = hidden_states(params, ids[b], sizes, precision,
                             None if routing is None else routing[b])
        rows.append(_mm(precision)("td,dv->tv", x, params["head"]))
    return jnp.stack(rows)


def _sizes_key(sizes):
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in sizes.items()
        if isinstance(v, (int, float, bool, list, tuple))))


@partial(jax.jit, static_argnames=("sizes_key", "precision"))
def _replay(params, ids, alt, routing, sizes_key, precision):
    sizes = dict(sizes_key)
    x, short = hidden_states(params, ids, sizes, precision, routing)
    mm = _mm(precision)
    T = ids.shape[0]
    block = min(ROW_BLOCK, T)
    # position t is judged by the token that follows it; the last by none
    nxt = jnp.concatenate([ids[1:], ids[:1]]).reshape(-1, block)
    nxt_alt = jnp.concatenate([alt[1:], alt[:1]]).reshape(-1, block)

    def rows(a):
        xb, tok, tok_alt = a
        lg = mm("td,dv->tv", xb, params["head"])                # [block, V]
        best = jnp.max(lg, axis=-1)
        pick = lambda t: jnp.take_along_axis(lg, t[:, None], -1)[:, 0]
        return (best - pick(tok), best - pick(tok_alt),
                jnp.argmax(lg, axis=-1).astype(jnp.int32))

    gap, gap_alt, best = jax.lax.map(
        rows, (x.reshape(-1, block, x.shape[-1]), nxt, nxt_alt))
    return (gap.reshape(-1)[:-1], gap_alt.reshape(-1)[:-1],
            best.reshape(-1)[:-1], short)


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

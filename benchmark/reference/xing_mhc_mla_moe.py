"""Plain reference of Xing4.0-29B-A4B's block (`config.json` with
`model_type: xing4_0`): DeepSeek-V3's latent attention with a low-rank
query under YaRN and sigmoid-routed experts beside a shared one, on a
residual of FOUR streams mixed by manifold-constrained hyper-connections
(arXiv:2512.24880 over arXiv:2409.19606). Hugging Face `transformers`'
`modeling_deepseek_v3.py` for the attention and the experts; the two
papers for the residual.

    X = [e; e; e; e],  e = E[id]                     (a token's state: [4, C])
    for l in layers, for (F, phi, b, a) in ((Attn_l, hc_attn_l), (FFN_l, hc_ffn_l)):
        x~      = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)        (all 4 C numbers, no gain)
        H~_pre  = a_pre  (x~ phi_pre)  + b_pre                  [4]
        H~_post = a_post (x~ phi_post) + b_post                 [4]
        H~_res  = a_res  mat(x~ phi_res) + b_res                [4, 4], clamped to [-30, 30]
        H_pre = sigmoid(H~_pre);  H_post = 2 sigmoid(H~_post)
        M = exp(H~_res); 20 times: every column / (its sum + hc_eps), then every row / (its sum + hc_eps)
        h  = H_pre X                                            [C]
        X  = M X + H_post^T F(rmsnorm(h, g))                    [4, C]
    logits = rmsnorm(sum of the 4 streams, g_f) W_out

    Attn(h):  q = rmsnorm(h Wq_a, gq) Wq_b            -> [T, 32, 192] = [q_nope 128 | q_rope 64]
              [c | kr] = h Wkv_a                      -> [T, 512 + 64];  c = rmsnorm(c, gkv)
              q_rope, kr = rope(q_rope), rope(kr)     (pairs (2i, 2i+1), YaRN's table; kr ONE key)
              k_nope = c Wk_b, v = c Wv_b
              a = causal softmax((q_nope . k_nope + q_rope . kr) mscale^2 / sqrt(192)) v;  a Wo
    FFN(h):   l < first_k_dense_replace: swiglu(h)
              else s = sigmoid(h Wg); sel = top4(s + b) of all 64 (one group)
                   g = 2 s[sel] / (sum(s[sel]) + 1e-20)
                   sum_e g_e swiglu_e(h) + swiglu_shared(h)

YaRN (`_compute_yarn_parameters`): theta 10000 over the 64-wide rope part,
frequencies that turn fewer than `beta_slow` = 1 times over the original
4,096 positions divided by `factor` = 64, those that turn more than
`beta_fast` = 32 times kept, a linear ramp between; cos and sin times
mscale(64, mscale) / mscale(64, mscale_all_dim) = 1; the scores times
mscale(64, mscale_all_dim)^2 = (0.1 ln 64 + 1)^2 = 2.0047.

THE EXPANDED FORM ONLY, no cache, no absorbed product, no kernel, no
batching, nothing imported from the program. Straightforward `jax.numpy`
in float32, products at `highest` precision, the streams a real axis
[T, 4, C], the Sinkhorn loop a Python loop over a [T, 4, 4] array, the
experts a plain loop over e. Each layer's weights are cast to float32 as
the layer is used; attention rows and the head's rows are taken in blocks
so that 16,896 positions fit beside 9.6 GB of weights. What the config
does not state is listed in the configuration's file under `assumed`.

Departures from a textbook statement, each for the comparison's sake:

  * weights are random normals of std 0.02, the experts' bias of std 0.1,
    the norms' gains 1 + 0.1 normal, and the hyper-connections FAR from
    their published start (scalars a = 0.01, a near-constant mix): `hc_bias`
    normal of std 1 and `hc_scale` 0.5 + 0.05 normal, so that the three H's
    move with the token, exp(H~_res) is far from doubly stochastic before
    the loop, and a dropped term or a loop cut short shows;
  * `precision="fp8"` is the CONTROL, not the reference: every product's
    operands rounded to float8_e4m3fn with a per-tensor scale (the
    hyper-connections' small product with phi among them);
  * `routing` (replay): the experts of each position are GIVEN (what the
    program chose), the weights still come from the reference's own
    scores; `replay()` also returns how far each given expert's biased
    score lies below the reference's own 4th best (reference/lfm2_moe.py
    says why).

Layout of the weight tree (what both the program and this file read):
`embed [V, D]  head [D, V]  norm [D]  layers: list of {input_layernorm,
post_attention_layernorm [D], attn {wq_a [D, 768], q_a_layernorm [768],
wq_b [768, H 192], wkv_a [D, 576], kv_a_layernorm [512], wk_b [512, H,
128], wv_b [512, H, 128], wo [H 128, D]}, hc_attn and hc_ffn {phi [4 D,
24], hc_bias [24], hc_scale [3]}, ffn {w1, w3 [D, F], w2 [F, D]} | {wg
[D, E], bias [E], w1, w3 [E, D, Fm], w2 [E, Fm, D], shared {w1, w3 [D,
Fm], w2 [Fm, D]}}}`. phi's columns are [pre 4 | post 4 | res 16, row-major
(row i, column j at 8 + 4 i + j)], stream j of vec(X) its rows j D .. (j +
1) D; hc_bias the same 24, hc_scale (a_pre, a_post, a_res). A configuration
without `hc_mult`, `q_lora_rank` or `rope_scaling` (tests take each alone)
runs the plain sum, `wq [D, H 192]`, the plain table.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0
ROW_BLOCK = 512         # attention rows, and rows of the head, at a time


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)),
                              seed // (2**31 - 1))


def hc_shapes(sizes: dict) -> dict:
    n, D = sizes["hc_mult"], sizes["hidden_size"]
    return {"phi": (n * D, n * (n + 2)), "hc_bias": (n * (n + 2),),
            "hc_scale": (3,)}


def layer_shapes(sizes: dict, l: int) -> dict:
    D, H, C = sizes["hidden_size"], sizes["num_attention_heads"], \
        sizes["kv_lora_rank"]
    dn, dr, dv = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], \
        sizes["v_head_dim"]
    attn = {"wkv_a": (D, C + dr), "kv_a_layernorm": (C,),
            "wk_b": (C, H, dn), "wv_b": (C, H, dv), "wo": (H * dv, D)}
    r = sizes.get("q_lora_rank")
    if r:
        attn.update(wq_a=(D, r), q_a_layernorm=(r,),
                    wq_b=(r, H * (dn + dr)))
    else:
        attn["wq"] = (D, H * (dn + dr))
    out = {"input_layernorm": (D,), "post_attention_layernorm": (D,),
           "attn": attn}
    if sizes.get("hc_mult"):
        out["hc_attn"], out["hc_ffn"] = hc_shapes(sizes), hc_shapes(sizes)
    if l < sizes["first_k_dense_replace"]:
        F = sizes["intermediate_size"]
        out["ffn"] = {"w1": (D, F), "w3": (D, F), "w2": (F, D)}
    else:
        E, F = sizes["n_routed_experts"], sizes["moe_intermediate_size"]
        Fs = sizes["n_shared_experts"] * F
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
        elif name == "hc_scale":
            leaf = 0.5 + 0.05 * z
        else:
            leaf = {"bias": 0.1, "hc_bias": 1.0}.get(name, std) * z
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


def mscale(factor: float, m: float) -> float:
    """DeepSeek's `yarn_get_mscale`."""
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope_table(sizes: dict):
    """(inv_freq [dr / 2], factor on cos and sin) of the rope part."""
    d, theta = int(sizes["qk_rope_head_dim"]), float(sizes["rope_theta"])
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    y = sizes.get("rope_scaling")
    if not y:
        return inv, 1.0
    if "attention_factor" in y or not (y.get("mscale")
                                       and y.get("mscale_all_dim")):
        raise NotImplementedError(
            f"rope_scaling {y}: without `mscale` and `mscale_all_dim` both "
            f"set, or with an `attention_factor`, transformers scales cos "
            f"and sin otherwise (0.1 ln(factor) + 1, or the factor given)")
    orig = y["original_max_position_embeddings"]

    def turns_at(n):        # the index whose frequency turns n times
        return d * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(turns_at(y.get("beta_fast", 32))), 0)
    high = min(math.ceil(turns_at(y.get("beta_slow", 1))), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    factor = mscale(y["factor"], y["mscale"]) \
        / mscale(y["factor"], y["mscale_all_dim"])
    return inv / y["factor"] * ramp + inv * (1.0 - ramp), factor


def score_scale(sizes: dict) -> float:
    scale = 1.0 / math.sqrt(sizes["qk_nope_head_dim"]
                            + sizes["qk_rope_head_dim"])
    y = sizes.get("rope_scaling")
    if y:
        scale *= mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return scale


def _rope(x, sizes):
    """x [T, ..., d], positions 0 .. T-1, adjacent pairs (2i, 2i+1)
    rotated by position x the table's frequency i."""
    T, d = x.shape[0], x.shape[-1]
    inv, factor = rope_table(sizes)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def attention_layer(h, p, sizes, mm):
    """h [T, D] -> y [T, D]: latent attention in its expanded form."""
    T, _ = h.shape
    H, C = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    dn, dr = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    eps = float(sizes["rms_norm_eps"])
    if sizes.get("q_lora_rank"):
        q = mm("tr,re->te", _rmsnorm(mm("td,dr->tr", h, p["wq_a"]),
                                     p["q_a_layernorm"], eps), p["wq_b"])
    else:
        q = mm("td,de->te", h, p["wq"])
    q = q.reshape(T, H, dn + dr)
    ckr = mm("td,de->te", h, p["wkv_a"])
    c = _rmsnorm(ckr[:, :C], p["kv_a_layernorm"], eps)
    kr = _rope(ckr[:, C:], sizes)                              # [T, dr]
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], sizes)
    k_nope = mm("tc,chd->thd", c, p["wk_b"])
    v = mm("tc,chd->thd", c, p["wv_b"])
    scale = score_scale(sizes)
    block = min(ROW_BLOCK, T)
    if T % block:
        raise ValueError(f"{T} positions are not whole blocks of {block}")

    def rows(r):
        at = r * block
        qn = jax.lax.dynamic_slice_in_dim(q_nope, at, block, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, at, block, 0)
        s = (mm("qhd,khd->hqk", qn, k_nope) + mm("qhd,kd->hqk", qr, kr)) \
            * scale
        qi = at + jnp.arange(block)[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= qi, s, -1e30)
        return mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    a = jax.lax.map(rows, jnp.arange(T // block)).reshape(T, -1)
    return mm("te,ed->td", a, p["wo"])


def swiglu(h, p, mm):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", h, p["w1"]))
              * mm("td,df->tf", h, p["w3"]), p["w2"])


def route(h, p, sizes, mm):
    """(s [T, E] scores, biased [T, E] what chooses, sel [T, k] own
    choice). One group: the group-limited step keeps it."""
    s = jax.nn.sigmoid(mm("td,de->te", h, p["wg"]))
    biased = s + p["bias"].astype(jnp.float32)
    _, sel = jax.lax.top_k(biased, int(sizes["num_experts_per_tok"]))
    return s, biased, sel


def moe_layer(h, p, sizes, mm, given=None):
    """h [T, D] -> (f [T, D], shortfall [T]). `given` [T, k] int: the
    experts to use (-1 in a row's first place = this row chooses its
    own)."""
    E = int(sizes["n_routed_experts"])
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
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    g = g * float(sizes.get("routed_scaling_factor", 1.0))
    # weight of expert e for token t: its g where chosen, else 0
    w = jnp.sum(jnp.where(sel[:, :, None] == jnp.arange(E), g[:, :, None],
                          0.0), axis=1)                          # [T, E]

    def one(f, xs):
        w1, w3, w2, we = xs
        y = swiglu(h, {"w1": w1, "w3": w3, "w2": w2}, mm)
        return f + we[:, None] * y, None

    f, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (p["w1"], p["w3"], p["w2"], w.T))
    return f + swiglu(h, p["shared"], mm), short


def hyper_coefficients(X, p, sizes, mm):
    """X [T, n, D] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n])."""
    T, n, _ = X.shape
    eps = float(sizes["hc_eps"])
    flat = X.reshape(T, -1)
    xt = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + eps)
    z = mm("td,dk->tk", xt, p["phi"])                           # [T, 24]
    a = p["hc_scale"].astype(jnp.float32)
    b = p["hc_bias"].astype(jnp.float32)
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    res = (a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(T, n, n)
    res = jnp.clip(res, float(sizes["mhc_h_res_clamp_min"]),
                   float(sizes["mhc_h_res_clamp_max"]))
    m = jnp.exp(res)
    for _ in range(int(sizes["hc_sinkhorn_iters"])):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)   # every column
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)   # every row
    return pre, post, m


def sublayer(X, hc, sizes, mm, branch):
    """One sub-layer on the residual: the streams [T, n, D] mixed by the
    hyper-connections `hc` round `branch`, or the plain sum on [T, D]."""
    if hc is None:
        return X + branch(X)
    pre, post, res = hyper_coefficients(X, hc, sizes, mm)
    h = jnp.einsum("tn,tnd->td", pre, X)
    return jnp.einsum("tij,tjd->tid", res, X) \
        + post[:, :, None] * branch(h)[:, None, :]


def hidden_states(params, ids, sizes, precision="f32", routing=None):
    """ids [T] -> (final-norm output [T, D] float32, shortfall [T, expert
    layers]). `routing` [T, expert layers, k] as in `moe_layer`."""
    mm = _mm(precision)
    eps = float(sizes["rms_norm_eps"])
    n = sizes.get("hc_mult")
    x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    if n:
        x = jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))
    shorts = []
    for l, p in enumerate(params["layers"]):
        def attention(h, p=p):
            return attention_layer(_rmsnorm(h, p["input_layernorm"], eps),
                                   p["attn"], sizes, mm)

        def feed_forward(h, p=p, l=l):
            h = _rmsnorm(h, p["post_attention_layernorm"], eps)
            if l < sizes["first_k_dense_replace"]:
                return swiglu(h, p["ffn"], mm)
            m = len(shorts)
            f, short = moe_layer(
                h, p["ffn"], sizes, mm,
                None if routing is None else routing[:, m].astype(jnp.int32))
            shorts.append(short)
            return f

        x = sublayer(x, p.get("hc_attn"), sizes, mm, attention)
        x = sublayer(x, p.get("hc_ffn"), sizes, mm, feed_forward)
    if n:
        x = jnp.sum(x, axis=1)
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


def _freeze(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return tuple(v) if isinstance(v, list) else v


def _thaw(v):
    # only `rope_scaling` is a group
    return dict(v) if isinstance(v, tuple) and v \
        and isinstance(v[0], tuple) else v


def _sizes_key(sizes):
    return tuple(sorted(
        (k, _freeze(v)) for k, v in sizes.items()
        if isinstance(v, (int, float, bool, list, tuple, dict))))


@partial(jax.jit, static_argnames=("sizes_key", "precision"))
def _replay(params, ids, alt, routing, sizes_key, precision):
    sizes = {k: _thaw(v) for k, v in sizes_key}
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

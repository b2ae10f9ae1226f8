"""Plain reference of the Mellum block as JetBrains' Mellum2-12B-A2.5B
configures it (`config.json` with `model_type: mellum`; what the config
does not spell out is listed under `assumed` in the configuration's file),
TRAINED: the loss, its gradient and AdamW.

    x = E[ids]
    for l in layers:
        a = rmsnorm(x, input_layernorm[l])
        q = a @ Wq -> [T, 32, 128];  k = a @ Wk, v = a @ Wv -> [T, 4, 128]
        q = rmsnorm(q, q_norm), k = rmsnorm(k, k_norm)     over the 128 of a head
        q, k = rope(q), rope(k)   rotate-half over the whole head, angle
              position x inv_freq_i, f_i = 500000^(-2i/128); a sliding layer
              inv_freq = f; a full layer YaRN: inv_freq_i = f_i / 16 x ramp_i
              + f_i x (1 - ramp_i), ramp_i = clip((i - 18) / (35 - 18), 0, 1),
              cos and sin both times 1.2772588722239782
        o_i = sum_j softmax_j(q_i . k_j / sqrt(128)) v_j    over j <= i, and on a
              sliding layer i - j < 1024; KV head h serves query heads 8h .. 8h+7
        x = x + o @ Wo
        m = rmsnorm(x, post_attention_layernorm[l])
        p = softmax(m @ Wr);  S = top8(p);  w_e = p_e / sum_{e' in S} p_e'
        x = x + sum_{e in S, e held} w_e (silu(m @ W1_e) * (m @ W3_e)) @ W2_e
    loss = mean over positions t < T-1 of  logsumexp(z_t) - z_t[ids[t+1]],
           z = rmsnorm(x, norm) @ W_head   over the vocabulary rows held

NO kernel, no sort, no batching, and nothing is imported from the program:
a mask says what a row attends to, K and V are repeated for every query
head, the experts are a plain loop over e (every held expert on every
token, then a masked weighted sum). Straightforward `jax.numpy` in float32,
products at `highest` precision. `experts_held` (a count n: ids 0 .. n-1,
or a list of ids) says which experts' weights exist; the router keeps all
its outputs and normalises over all 8 it chose, and what the absent experts
would add is left out, here as in the program. Attention rows and the
head's rows go in blocks of `row_block`, each recomputed in the backward
pass, one sequence at a time, so that three float32 steps of 16,384 tokens
fit beside 9.5 GB of state.

Departures from a textbook statement, each for the comparison's sake (as
reference/afmoe_window_moe.py's): weights random normals of std 0.02, the
norms' gains 1 + 0.1 normal (a dropped gain shows), made from the seed on
the device in one jitted call; `precision="bf16"` / `"fp8"` are CONTROLS,
not the reference (every product's operands rounded to that format);
`routing` (replay): the experts of each position are GIVEN (what the
program chose), the weights still come from the reference's own
probabilities, and the shortfall says how far each given expert's
probability lies below the reference's own 8th best; the optimizer is
AdamW in the Paddle form (reference/gpt_dense.py says which).

Layout of the weight tree (what both the program and this file read), a
layer's leaves stacked: `embed [V, D]  head [D, V]  norm [D]  layers:
{input_layernorm, post_attention_layernorm [L, D], attn {wq [L, D, H d],
wk, wv [L, D, Hkv d], wo [L, H d, D], q_norm, k_norm [L, d]}, ffn {wg [L,
D, E], w1, w3 [L, Eh, D, F], w2 [L, Eh, F, D]}}`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

DECAYED = ("embed", "head", "wq", "wk", "wv", "wo", "wg", "w1", "w3", "w2")
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0
SLIDING, FULL = "sliding_attention", "full_attention"


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)),
                              seed // (2**31 - 1))


def held_ids(sizes: dict) -> tuple:
    held = sizes.get("experts_held")
    if held is None:
        return tuple(range(int(sizes["num_experts"])))
    if isinstance(held, int):
        return tuple(range(held))
    return tuple(int(e) for e in held)


def weight_shapes(sizes: dict) -> dict:
    D, d, L = sizes["hidden_size"], sizes["head_dim"], \
        sizes["num_hidden_layers"]
    H, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    E, F, V = sizes["num_experts"], sizes["moe_intermediate_size"], \
        sizes["vocab_size"]
    Eh = len(held_ids(sizes))
    return {"embed": (V, D), "head": (D, V), "norm": (D,),
            "layers": {
                "input_layernorm": (L, D),
                "post_attention_layernorm": (L, D),
                "attn": {"wq": (L, D, H * d), "wk": (L, D, Hkv * d),
                         "wv": (L, D, Hkv * d), "wo": (L, H * d, D),
                         "q_norm": (L, d), "k_norm": (L, d)},
                "ffn": {"wg": (L, D, E), "w1": (L, Eh, D, F),
                        "w3": (L, Eh, D, F), "w2": (L, Eh, F, D)}}}


def make_weights(sizes: dict, seed: int, dtype, out_shardings=None,
                 reshape=None):
    """The weight tree from the seed, on the device, in one jitted call,
    in `dtype`. `out_shardings` place the leaves; `reshape` is the GPT
    trainer's (a pipeline's stacking) and must be None here."""
    if reshape is not None:
        raise ValueError("this reference knows no pipeline stacking")
    shapes = weight_shapes(sizes)
    std = float(sizes.get("initializer_range", 0.02))

    def build(key):
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda s: isinstance(s, tuple))
        out = []
        for i, (path, shape) in enumerate(flat):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            name = path[-1].key
            out.append((1.0 + 0.1 * z if name.endswith("norm")
                        else std * z).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    kw = {} if out_shardings is None else {"out_shardings": out_shardings}
    return jax.jit(build, **kw)(seed_key(seed))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _quantize(a, dtype, top):
    a = a.astype(jnp.float32)
    scale = top / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    return (a * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _round_fp8(a):
    """Round to float8 with a per-tensor scale: e4m3 forward and, as fp8
    training does, e5m2 for the cotangent on the way back."""
    return _quantize(a, _F8, _F8_MAX)


_round_fp8.defvjp(lambda a: (_quantize(a, _F8, _F8_MAX), None),
                  lambda _res, g: (_quantize(g, jnp.float8_e5m2, 57344.0),))


@jax.custom_vjp
def _round_bf16(a):
    """Round to bfloat16, the cotangent too."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


_round_bf16.defvjp(
    lambda a: (a.astype(jnp.bfloat16).astype(jnp.float32), None),
    lambda _res, g: (g.astype(jnp.bfloat16).astype(jnp.float32),))


def _mm(precision: str):
    """The matrix product of this precision, as `mm(spec, a, b)`."""
    hi = jax.lax.Precision.HIGHEST
    f = lambda a: a.astype(jnp.float32)
    rnd = {"f32": f, "bf16": lambda a: _round_bf16(f(a)),
           "fp8": lambda a: _round_fp8(f(a))}
    if precision not in rnd:
        raise ValueError(f"unknown precision {precision!r}")
    r = rnd[precision]
    return lambda spec, a, b: jnp.einsum(spec, r(a), r(b), precision=hi)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope_table(sizes: dict, kind: str):
    """(inv_freq [d/2], factor) of a layer kind, the closed form above
    from the file's `rope_parameters`."""
    d = int(sizes["head_dim"])
    rp = sizes["rope_parameters"][kind]
    theta = float(rp["rope_theta"])
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / d)
    if rp["rope_type"] == "default":
        return f, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"unknown rope_type {rp['rope_type']!r}")
    orig = float(rp["original_max_position_embeddings"])
    at = lambda turns: d * math.log(orig / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(at(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(at(float(rp["beta_slow"]))), d - 1)
    ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return f / float(rp["factor"]) * ramp + f * (1.0 - ramp), \
        float(rp["attention_factor"])


def _rope(x, inv_freq, factor):
    """x [T, heads, d], positions 0 .. T-1, rotate-half."""
    T, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_layer(h, p, sizes, mm, sliding, row_block):
    """h [T, D] -> y [T, D]: grouped-query attention, every position
    present. `sliding` (a traced flag: the layers run as one scan) says
    which kind of layer this is: the band and the plain frequencies, or
    the whole triangle and YaRN's."""
    T, _ = h.shape
    H, Hkv, d = sizes["num_attention_heads"], sizes["num_key_value_heads"], \
        sizes["head_dim"]
    eps = float(sizes["rms_norm_eps"])
    q = _rmsnorm(mm("td,de->te", h, p["wq"]).reshape(T, H, d), p["q_norm"],
                 eps)
    k = _rmsnorm(mm("td,de->te", h, p["wk"]).reshape(T, Hkv, d),
                 p["k_norm"], eps)
    v = mm("td,de->te", h, p["wv"]).reshape(T, Hkv, d)
    tables = [rope_table(sizes, kind) for kind in (SLIDING, FULL)]
    inv_freq = jnp.where(sliding, tables[0][0], tables[1][0])
    factor = jnp.where(sliding, tables[0][1], tables[1][1])
    q, k = _rope(q, inv_freq, factor), _rope(k, inv_freq, factor)
    # a full layer's band is wider than the sequence
    window = jnp.where(sliding, int(sizes["sliding_window"]), T)
    # every query head its own key and value: KV head h serves 8h .. 8h+7
    k, v = jnp.repeat(k, H // Hkv, axis=1), jnp.repeat(v, H // Hkv, axis=1)
    block = min(int(row_block), T)
    if T % block:
        raise ValueError(f"{T} positions are not whole blocks of {block}")

    @jax.checkpoint
    def rows(qb, at, k, v):
        s = mm("qhd,khd->hqk", qb, k) / math.sqrt(d)
        qi = at + jnp.arange(block)[:, None]
        kj = jnp.arange(T)[None, :]
        ok = (kj <= qi) & (qi - kj < window)
        # added, not selected: a select keeps its mask for the backward
        # pass, of every block at once (2 GiB at 8,192 positions)
        return mm("hqk,khd->qhd", jax.nn.softmax(
            s + jnp.where(ok, 0.0, -1e30), axis=-1), v)

    o = jax.lax.map(lambda a: rows(a[0], a[1], k, v),
                    (q.reshape(T // block, block, H, d),
                     jnp.arange(T // block) * block)).reshape(T, -1)
    return mm("te,ed->td", o, p["wo"])


def moe_layer(h, p, sizes, mm, given=None):
    """h [T, D] -> (f [T, D], shortfall [T]). `given` [T, k] int: the
    experts to use. The part of the layer that the held experts give."""
    E, k = int(sizes["num_experts"]), int(sizes["num_experts_per_tok"])
    pr = jax.nn.softmax(mm("td,de->te", h, p["wg"]), axis=-1)
    top, sel = jax.lax.top_k(pr, k)
    short = jnp.zeros(h.shape[:1], jnp.float32)
    if given is not None:
        got = jnp.take_along_axis(pr, given, -1)
        short = jnp.min(top, -1) - jnp.min(got, -1)
        sel, top = given, got
    g = top
    if sizes.get("norm_topk_prob", True):
        g = g / jnp.sum(g, -1, keepdims=True)
    # weight of expert e for token t: its g where chosen, else 0
    w = jnp.sum(jnp.where(sel[:, :, None] == jnp.arange(E), g[:, :, None],
                          0.0), axis=1)                          # [T, E]
    w = w[:, jnp.asarray(held_ids(sizes))]                       # [T, Eh]

    @jax.checkpoint
    def expert(h, w1, w3, w2, we):
        y = mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", h, w1))
               * mm("td,df->tf", h, w3), w2)
        return we[:, None] * y

    f, _ = jax.lax.scan(lambda f, xs: (f + expert(h, *xs), None),
                        jnp.zeros_like(h), (p["w1"], p["w3"], p["w2"], w.T))
    return f, short


def hidden_states(params, ids, sizes, precision="f32", routing=None,
                  row_block=256):
    """ids [B, T] -> (final-norm output [B, T, D] float32, shortfall [L,
    B, T]). `routing` [L, B, T, k]: the experts to use, layer by layer.
    The layers are one scan over their stacked weights, each recomputed
    in the backward pass; inside a layer the sequences go one at a time."""
    mm = _mm(precision)
    eps = float(sizes["rms_norm_eps"])
    x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    sliding = jnp.asarray([kind == SLIDING for kind in sizes["layer_types"]])

    @jax.checkpoint
    def layer(x, xs):
        p, is_sliding, given = xs

        @jax.checkpoint
        def one(a):
            xb, gb = a
            h = _rmsnorm(xb, p["input_layernorm"], eps)
            xb = xb + attention_layer(h, p["attn"], sizes, mm, is_sliding,
                                      row_block)
            h = _rmsnorm(xb, p["post_attention_layernorm"], eps)
            f, short = moe_layer(h, p["ffn"], sizes, mm, gb)
            return xb + f, short
        return jax.lax.map(one, (x, given))

    x, short = jax.lax.scan(layer, x, (params["layers"], sliding, routing))
    return _rmsnorm(x, params["norm"], eps), short


def batch_loss(params, ids, sizes, precision="f32", routing=None,
               row_block=256):
    """(mean next-token cross-entropy over ids [B, T], widest shortfall).
    `routing` [L, B T, k] as the program hands it back. The head's rows
    in blocks, each recomputed in the backward pass."""
    B, T = ids.shape
    if routing is not None:
        routing = routing.astype(jnp.int32).reshape(
            routing.shape[0], B, T, -1)
    x, short = hidden_states(params, ids, sizes, precision, routing,
                             row_block)
    mm = _mm(precision)
    block = min(int(row_block), T)
    nxt = jnp.concatenate([ids[:, 1:], ids[:, :1]], axis=1)
    # the last position of a sequence predicts nothing
    live = jnp.broadcast_to(jnp.arange(T) < T - 1, (B, T))

    @jax.checkpoint
    def rows(xb, tok, on):
        z = mm("td,dv->tv", xb, params["head"])
        gold = jnp.take_along_axis(z, tok[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(on, jax.nn.logsumexp(z, axis=-1) - gold,
                                 0.0))

    parts = jax.lax.map(lambda a: rows(*a), (
        x.reshape(-1, block, x.shape[-1]), nxt.reshape(-1, block),
        live.reshape(-1, block)))
    return jnp.sum(parts) / (B * (T - 1)), jnp.max(short)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def _leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))),
        tree)


def train_steps(params, batches, sizes, hyper, precision="f32",
                row_block=256, routing=None):
    """Follow `len(batches)` AdamW steps from float32 `params`.

    Returns (losses, norm of each leaf of the FIRST gradient as the
    optimizer gets it, i.e. after clipping, norm of each leaf of the
    parameters' change over all the steps, widest shortfall over the
    steps). `routing`: one [L, B T, k] a step, the experts the program
    chose there (replay); None lets every step choose its own."""
    lr, wd = float(hyper["lr"]), float(hyper["weight_decay"])
    b1, b2 = float(hyper["beta1"]), float(hyper["beta2"])
    eps, clip = float(hyper["epsilon"]), hyper.get("grad_clip_norm")

    @jax.jit
    def loss_grad(p, ids, given):
        (l, short), g = jax.value_and_grad(
            lambda q: batch_loss(q, ids, sizes, precision, given,
                                 row_block), has_aux=True)(p)
        return l, short, g

    def update(p, g, m1, m2, t):
        if clip:
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in
                              jax.tree_util.tree_leaves(g)))
            g = jax.tree_util.tree_map(
                lambda x: x * jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12)),
                g)
        lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)

        def one(path, p, g, m1, m2):
            decay = wd if path[-1].key in DECAYED else 0.0
            m1n = b1 * m1 + (1 - b1) * g
            m2n = b2 * m2 + (1 - b2) * jnp.square(g)
            pn = p * (1.0 - lr * decay) - lr_t * m1n / (jnp.sqrt(m2n) + eps)
            return pn, m1n, m2n

        out = jax.tree_util.tree_map_with_path(one, p, g, m1, m2)
        pick = lambda i: jax.tree_util.tree_map(
            lambda _p, o: o[i], p, out)
        return pick(0), pick(1), pick(2), _leaf_norms(g)

    # the caller's tree is the start and is kept; every later tree is
    # this call's own and is updated in place. While a step's gradient is
    # computed the moments wait on the host: start, parameters, gradient
    # and the step's activations are then all the device holds
    updates = (jax.jit(update, donate_argnums=(1, 2, 3)),
               jax.jit(update, donate_argnums=(0, 1, 2, 3)))
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
    start = params
    parked = None
    losses, first_grad, worst = [], None, 0.0
    for t, ids in enumerate(batches, 1):
        given = None if routing is None else jnp.asarray(routing[t - 1])
        loss, short, grad = loss_grad(params, jnp.asarray(ids), given)
        losses.append(float(loss))
        worst = max(worst, float(short))
        m1, m2 = (zeros(params), zeros(params)) if parked is None \
            else jax.device_put(parked)
        params, m1, m2, gnorms = updates[t > 1](params, grad, m1, m2,
                                                float(t))
        del grad
        if first_grad is None:
            first_grad = gnorms
        if t < len(batches):
            parked = jax.device_get((m1, m2))
        del m1, m2
    change = _leaf_norms(jax.jit(lambda a, b: jax.tree_util.tree_map(
        jnp.subtract, a, b))(params, start))
    return losses, first_grad, change, worst

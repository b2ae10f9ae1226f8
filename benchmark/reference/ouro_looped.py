"""Plain reference of the Ouro looped decoder (ByteDance Ouro-2.6B,
`config.json` with `model_type: ouro`; the public `modeling_ouro.py` beside
it for the equations the config does not spell out).

    x = E[ids]
    for t in range(total_ut_steps):            # the SAME layers every pass
        for l in range(num_hidden_layers):
            h = rmsnorm(x, input_layernorm[l])
            q, k, v = h Wq[l], h Wk[l], h Wv[l]          (16 / 16 heads of 128)
            q, k = rope(q, theta), rope(k, theta)
            a = causal softmax(q k^T / sqrt(128)) v  @ Wo[l]     (this pass's k, v)
            x = x + rmsnorm(a, input_layernorm_2[l])
            h = rmsnorm(x, post_attention_layernorm[l])
            m = (silu(h W1[l]) * (h W3[l])) W2[l]
            x = x + rmsnorm(m, post_attention_layernorm_2[l])
        x = rmsnorm(x, norm);  state[t] = x               (norm INSIDE the loop)
        lam[t] = sigmoid(x . gate_w + gate_b)
    p[t] = lam[t] prod_{j<t}(1 - lam[j]) for t < last;  p[last] = prod_{j<last}(1 - lam[j])
    served = state[first t with cumsum(p)[t] >= early_exit_threshold, else last]
    logits = served @ head                                (untied head)

Straightforward `jax.numpy` in float32, products at `highest` precision,
no cache, no kernels, nothing imported from the program. The layers are a
stack scanned once a pass; each layer's weights are cast to float32 as the
layer is used, so a bfloat16 tree costs no float32 copy (2.67B parameters
are 10.7 GB in float32: they never exist at once). Attention rows are taken
in blocks. What the config does not state and is this reading of
`modeling_ouro.py` (the configuration's file lists each under `assumed`):
four norms a layer, the second and fourth on the branch's OUTPUT; no
projection biases; the final norm inside the loop; the cache index t L + l
(here: each pass attends over its own k, v, which is the same statement
without a cache); the gate Linear(hidden -> 1) with a sigmoid and the
stick-breaking distribution above.

Departures from a textbook statement, each for the comparison's sake:

  * weights are random normals of `initializer_range`; the norms' gains
    1 + 0.1 normal (round one and NOT at one: a program that leaves a gain
    out, or uses the wrong one of four, then disagrees); the gate's weight
    normal of 1/sqrt(hidden) and its bias zero, so that x . w is of size
    one and no lam saturates; made from the seed on the device, one jitted
    call a leaf, a stacked leaf one layer at a time;
  * `precision="fp8"` is the CONTROL, not the reference: every product's
    operands rounded to float8_e4m3fn with a per-tensor scale;
  * `precision="bf16"` is a second control, of the configuration's OWN
    precision: every product's operands rounded to bfloat16, everything
    else float32. What it reads against float32 is what the stated
    precision costs through 192 layer applications whatever the program:
    a sound bfloat16 program reads about as much (PERF.md, section 6);
  * `n_passes` (tests): stop the loop after so many passes.

Layout of the weight tree (what both the program and this file read):
`embed [V, D]  head [D, V]  norm [D]  gate_w [D]  gate_b []  layers:
{input_layernorm, input_layernorm_2, post_attention_layernorm,
post_attention_layernorm_2 [L, D], wq [L, H d, D], wk, wv [L, Hkv d, D]
(out, in: as `nn.Linear` holds them), wo [L, H d, D], ffn {w1, w3 [L, D, F],
w2 [L, F, D]}}`.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0
ROW_BLOCK = 1024        # attention rows at a time
NORMS = ("input_layernorm", "input_layernorm_2", "post_attention_layernorm",
         "post_attention_layernorm_2")


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)),
                              seed // (2**31 - 1))


def weight_shapes(sizes: dict) -> dict:
    D, F = sizes["hidden_size"], sizes["intermediate_size"]
    L, d = sizes["num_hidden_layers"], sizes["head_dim"]
    Hq, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    layers = {n: (L, D) for n in NORMS}
    layers.update(wq=(L, Hq * d, D), wk=(L, Hkv * d, D), wv=(L, Hkv * d, D),
                  wo=(L, Hq * d, D),
                  ffn={"w1": (L, D, F), "w3": (L, D, F), "w2": (L, F, D)})
    return {"embed": (sizes["vocab_size"], D), "head": (D, sizes["vocab_size"]),
            "norm": (D,), "gate_w": (D,), "gate_b": (), "layers": layers}


@partial(jax.jit, static_argnames=("shape", "mean", "std", "dtype"))
def _leaf(key, shape, mean, std, dtype):
    def draw(k, s):
        return (mean + std * jax.random.normal(k, s, jnp.float32)
                ).astype(dtype)
    if len(shape) < 3:
        return draw(key, shape)
    # a stacked matrix: one layer at a time, so that the float32 draw of
    # the whole stack (2.2 GB for the widest) never exists
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
        if name == "norm" or name in NORMS:
            mean, scale = 1.0, 0.1
        elif name == "gate_w":
            mean, scale = 0.0, 1.0 / math.sqrt(sizes["hidden_size"])
        elif name == "gate_b":
            mean, scale = 0.0, 0.0
        else:
            mean, scale = 0.0, std
        out.append(_leaf(jax.random.fold_in(key, i), shape, mean, scale,
                         dtype))
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


def _rope(x, theta):
    """x [T, H, d], positions 0 .. T-1, rotate-half over the whole head."""
    T, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def attention(h, p, sizes, mm):
    """h [T, D] -> a Wo [T, D]: causal, over this call's own k and v."""
    T, _ = h.shape
    d = sizes["head_dim"]
    Hq, Hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    theta = float(sizes["rope_theta"])
    q = _rope(mm("td,ed->te", h, p["wq"]).reshape(T, Hq, d), theta)
    k = _rope(mm("td,ed->te", h, p["wk"]).reshape(T, Hkv, d), theta)
    v = mm("td,ed->te", h, p["wv"]).reshape(T, Hkv, d)
    # query head h reads KV head h // G
    k = jnp.repeat(k, Hq // Hkv, axis=1)
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    out = []
    for r in range(0, T, ROW_BLOCK):
        rows = slice(r, min(T, r + ROW_BLOCK))
        s = mm("qhd,khd->hqk", q[rows], k) / math.sqrt(d)
        qi = jnp.arange(rows.start, rows.stop)[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= qi, s, -1e30)
        out.append(mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v))
    a = jnp.concatenate(out, 0).reshape(T, Hq * d)
    return mm("te,ed->td", a, p["wo"])


def swiglu(h, p, mm):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", h, p["w1"]))
              * mm("td,df->tf", h, p["w3"]), p["w2"])


def one_layer(x, p, sizes, mm):
    """The sandwich layer: a norm before each branch and one on its
    output."""
    eps = float(sizes["rms_norm_eps"])
    a = attention(_rmsnorm(x, p["input_layernorm"], eps), p, sizes, mm)
    x = x + _rmsnorm(a, p["input_layernorm_2"], eps)
    m = swiglu(_rmsnorm(x, p["post_attention_layernorm"], eps), p["ffn"], mm)
    return x + _rmsnorm(m, p["post_attention_layernorm_2"], eps)


def exit_distribution(lam):
    """lam [passes, T] -> p [passes, T], summing to one over the passes."""
    n = lam.shape[0]
    p, left = [], jnp.ones_like(lam[0])
    for t in range(n - 1):
        p.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(p + [left])


def passes(params, ids, sizes, precision="f32", n_passes=None):
    """ids [T] -> (state [passes, T, D] after the final norm of each pass,
    lam [passes, T])."""
    mm = _mm(precision)
    eps = float(sizes["rms_norm_eps"])
    x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    states, lams = [], []
    for _t in range(int(n_passes or sizes["total_ut_steps"])):
        x, _ = jax.lax.scan(
            lambda x, p: (one_layer(x, p, sizes, mm), None), x,
            params["layers"])
        x = _rmsnorm(x, params["norm"], eps)
        states.append(x)
        lams.append(jax.nn.sigmoid(
            mm("td,d->t", x, params["gate_w"])
            + params["gate_b"].astype(jnp.float32)))
    return jnp.stack(states), jnp.stack(lams)


def served_state(states, lam, threshold: float):
    """The state of the first pass at which the cumulative exit
    probability reaches the threshold, the last pass where none does."""
    cum = jnp.cumsum(exit_distribution(lam), axis=0)          # [passes, T]
    last = lam.shape[0] - 1
    reached = cum >= threshold
    first = jnp.where(jnp.any(reached, 0), jnp.argmax(reached, 0), last)
    return jnp.take_along_axis(states, first[None, :, None], axis=0)[0], first


def hidden_states(params, ids, sizes, precision="f32", n_passes=None):
    states, lam = passes(params, ids, sizes, precision, n_passes)
    return served_state(states, lam,
                        float(sizes.get("early_exit_threshold", 1.0)))[0]


def logits(params, ids, sizes, precision="f32", n_passes=None):
    """ids [B, T] -> logits [B, T, V]; one sequence at a time."""
    mm = _mm(precision)
    return jnp.stack([
        mm("td,dv->tv", hidden_states(params, ids[b], sizes, precision,
                                      n_passes), params["head"])
        for b in range(ids.shape[0])])


def _sizes_key(sizes):
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, (int, float, bool))))


@partial(jax.jit, static_argnames=("sizes_key", "precision"))
def _gaps(params, ids, alt, sizes_key, precision):
    sizes = dict(sizes_key)
    x = hidden_states(params, ids, sizes, precision)
    lg = _mm(precision)("td,dv->tv", x, params["head"])[:-1]     # [T-1, V]
    best = jnp.max(lg, axis=-1)
    pick = lambda tok: jnp.take_along_axis(lg, tok[:, None], -1)[:, 0]
    return (best - pick(ids[1:]), best - pick(alt[1:]),
            jnp.argmax(lg, axis=-1).astype(jnp.int32))


def next_token_gaps(params, ids, sizes, precision="f32", alt=None):
    """For one padded sequence ids [T]: at each position t < T-1, how far
    the logit of the token that FOLLOWS (ids[t+1]) lies below the best
    logit, the same for `alt[t+1]`, and the best token. All float32 /
    int32 arrays of length T-1. Padding after the real tokens does not
    reach earlier positions (causal)."""
    ids = jnp.asarray(ids, jnp.int32)
    alt = ids if alt is None else jnp.asarray(alt, jnp.int32)
    return _gaps(params, ids, alt, _sizes_key(sizes), precision)

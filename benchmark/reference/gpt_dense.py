"""Plain reference of the dense GPT decoder (GPT-2/GPT-3 block, Brown et
al. 2020, arXiv:2005.14165 section 2.1; Radford et al. 2019): learned
position embeddings, pre-LayerNorm blocks, biased projections, tanh-GELU
feed-forward of 4x width, output head tied to the token embedding.

Straightforward `jax.numpy` in float32, no kernels, no cache, no batching
tricks, and nothing imported from the program. Matrix products run at
`highest` precision (on a TPU a float32 product otherwise runs in
bfloat16 passes). Departures from a textbook statement, each because the
configuration under test states it:

  * weights are random normals (std 0.02; out- and down-projections
    scaled by 1/sqrt(2L), the GPT-2 residual scaling) made here from the
    seed, on the device, in one jitted call;
  * the optimizer is AdamW in the Paddle form: decoupled decay
    p <- p (1 - lr wd) on matrices and embeddings only, epsilon added to
    sqrt(m2) before bias correction, global-norm clipping first;
  * `precision="fp8"` is the CONTROL, not the reference: every product's
    operands are rounded to float8_e4m3fn (per-tensor scale to the
    format's range), the step below bfloat16 that would tempt a later PR.

Layout of the weight tree (what both the program and this file read):
`wte [V,D] wpe [P,D] lnf_s lnf_b [D] blocks{ln1_s ln1_b wq bq wk bk wv bv
wo bo ln2_s ln2_b w_up b_up w_down b_down}`, block leaves stacked [L, ...].
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

DECAYED = ("wte", "wpe", "wq", "wk", "wv", "wo", "w_up", "w_down")
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**63."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)),
                              seed // (2**31 - 1))


def weight_shapes(sizes: dict) -> dict:
    D, F, L = (sizes["hidden_size"], sizes["intermediate_size"],
               sizes["num_layers"])
    V, P = sizes["vocab_size"], sizes["max_position_embeddings"]
    blocks = {"ln1_s": (L, D), "ln1_b": (L, D), "wq": (L, D, D),
              "bq": (L, D), "wk": (L, D, D), "bk": (L, D),
              "wv": (L, D, D), "bv": (L, D), "wo": (L, D, D),
              "bo": (L, D), "ln2_s": (L, D), "ln2_b": (L, D),
              "w_up": (L, D, F), "b_up": (L, F), "w_down": (L, F, D),
              "b_down": (L, D)}
    return {"wte": (V, D), "wpe": (P, D), "blocks": blocks,
            "lnf_s": (D,), "lnf_b": (D,)}


def make_weights(sizes: dict, seed: int, dtype, out_shardings=None,
                 reshape=None):
    """The weight tree from the seed, on the device, in one jitted call,
    in `dtype`. `reshape(tree)` may rearrange leaves inside the call (a
    pipeline's per-stage stacking) and `out_shardings` place them."""
    shapes = weight_shapes(sizes)
    L = sizes["num_layers"]
    std = float(sizes.get("initializer_range", 0.02))

    def build(key):
        flat, treedef = jax.tree_util.tree_flatten_with_path(
            shapes, is_leaf=lambda s: isinstance(s, tuple))
        out = []
        for i, (path, shape) in enumerate(flat):
            name = path[-1].key
            if name.endswith("_s"):
                leaf = jnp.ones(shape, jnp.float32)
            elif name.startswith("b") or name.endswith("_b"):
                leaf = jnp.zeros(shape, jnp.float32)
            else:
                leaf = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                if name in ("wo", "w_down"):
                    leaf = leaf / math.sqrt(2 * L)
            out.append(leaf.astype(dtype))
        tree = jax.tree_util.tree_unflatten(treedef, out)
        return reshape(tree) if reshape is not None else tree

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


def _mm(precision: str):
    """The matrix product of this precision, as `mm(spec, a, b)`."""
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(
            spec, a.astype(jnp.float32), b.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
    if precision == "fp8":
        return lambda spec, a, b: jnp.einsum(
            spec, _round_fp8(a), _round_fp8(b),
            precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


def _layer_norm(x, s, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * s + b


def _block(x, p, sizes, mm):
    """One pre-LN decoder block on x [B, T, D], float32."""
    H = sizes["num_heads"]
    eps = float(sizes.get("layer_norm_eps", 1e-5))
    B, T, D = x.shape
    d = D // H
    f = lambda a: a.astype(jnp.float32)
    h = _layer_norm(x, f(p["ln1_s"]), f(p["ln1_b"]), eps)
    q = (mm("btd,de->bte", h, p["wq"]) + f(p["bq"])).reshape(B, T, H, d)
    k = (mm("btd,de->bte", h, p["wk"]) + f(p["bk"])).reshape(B, T, H, d)
    v = (mm("btd,de->bte", h, p["wv"]) + f(p["bv"])).reshape(B, T, H, d)
    scores = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    mask = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    a = mm("bhqk,bkhd->bqhd", probs, v).reshape(B, T, D)
    x = x + mm("btd,de->bte", a, p["wo"]) + f(p["bo"])
    h = _layer_norm(x, f(p["ln2_s"]), f(p["ln2_b"]), eps)
    u = jax.nn.gelu(mm("btd,df->btf", h, p["w_up"]) + f(p["b_up"]),
                    approximate=True)
    return x + mm("btf,fd->btd", u, p["w_down"]) + f(p["b_down"])


def hidden_states(params, ids, sizes, precision="f32", remat=False):
    """ids [B, T] -> final-LayerNorm output [B, T, D], float32. Layers run
    one at a time over the stacked leaves (a scan), casting each layer's
    weights to float32 as it is used, so a bfloat16 tree costs no float32
    copy."""
    mm = _mm(precision)
    T = ids.shape[-1]
    x = jnp.take(params["wte"], ids, axis=0).astype(jnp.float32) \
        + params["wpe"][:T].astype(jnp.float32)
    step = lambda x, p: (_block(x, p, sizes, mm), None)
    if remat:
        step = jax.checkpoint(step)
    x, _ = jax.lax.scan(step, x, params["blocks"])
    return _layer_norm(x, params["lnf_s"].astype(jnp.float32),
                       params["lnf_b"].astype(jnp.float32),
                       float(sizes.get("layer_norm_eps", 1e-5)))


def logits(params, ids, sizes, precision="f32"):
    x = hidden_states(params, ids, sizes, precision)
    return _mm(precision)("btd,vd->btv", x, params["wte"])


@partial(jax.jit, static_argnames=("sizes_key", "precision"))
def _next_token_gaps(params, ids, alt, sizes_key, precision):
    sizes = dict(sizes_key)
    lg = logits(params, ids[None], sizes, precision)[0, :-1]    # [T-1, V]
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
    key = tuple(sorted((k, v) for k, v in sizes.items()
                       if isinstance(v, (int, float))))
    return _next_token_gaps(params, ids, alt, key, precision)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def batch_loss(params, ids, sizes, precision="f32"):
    """Mean next-token cross entropy over ids [B, T]."""
    x = hidden_states(params, ids, sizes, precision, remat=True)
    lg = _mm(precision)("btd,vd->btv", x, params["wte"])[:, :-1]
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, ids[:, 1:, None], -1)[..., 0]
    return jnp.mean(logz - gold)


def _leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))),
        tree)


def train_steps(params, batches, sizes, hyper, precision="f32",
                row_block=4):
    """Follow `len(batches)` AdamW steps from float32 `params`.

    Returns (losses, norm of each leaf of the FIRST gradient as the
    optimizer gets it, i.e. after clipping, norm of each leaf of the
    parameters' change over all the steps). The batch is walked in blocks
    of `row_block` rows with gradients summed, so the float32 activations
    fit beside the state."""
    lr, wd = float(hyper["lr"]), float(hyper["weight_decay"])
    b1, b2 = float(hyper["beta1"]), float(hyper["beta2"])
    eps, clip = float(hyper["epsilon"]), hyper.get("grad_clip_norm")
    key = tuple(sorted((k, v) for k, v in sizes.items()
                       if isinstance(v, (int, float))))

    @partial(jax.jit, donate_argnums=(2,))
    def block_grad(p, ids, acc, w):
        """Loss of one block of rows, and its gradient added to `acc` with
        the block's share `w` of the batch."""
        l, g = jax.value_and_grad(
            lambda q: batch_loss(q, ids, dict(key), precision))(p)
        return l, jax.tree_util.tree_map(lambda a, x: a + w * x, acc, g)

    @partial(jax.jit, donate_argnums=(1, 2, 3))
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

    start = params
    m1 = jax.tree_util.tree_map(jnp.zeros_like, params)
    m2 = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for t, ids in enumerate(batches, 1):
        ids = jnp.asarray(ids)
        n = ids.shape[0]
        loss = 0.0
        grad = jax.tree_util.tree_map(jnp.zeros_like, params)
        for r in range(0, n, row_block):
            blk = ids[r:r + row_block]
            w = blk.shape[0] / n
            l, grad = block_grad(params, blk, grad, w)
            loss = loss + w * float(l)
        losses.append(float(loss))
        params, m1, m2, gnorms = update(params, grad, m1, m2, float(t))
        if first_grad is None:
            first_grad = gnorms
    change = _leaf_norms(jax.tree_util.tree_map(jnp.subtract, params, start))
    return losses, first_grad, change

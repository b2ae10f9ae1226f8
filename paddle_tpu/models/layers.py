"""The sub-layers the decoder cores share, each written once: pure functions
of their weights and input, with no state (`routed_ffn` reads the routing
fields of whichever core's config it is handed).

models/lfm2.py, ouro.py, deepseek_v3.py, afmoe.py, jamba.py and mellum.py
each import what they use from here and nothing from one another
(tests/test_model_cores.py). A core that uses a name has it as a module
attribute, which is where the benchmark's fault tools replace it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.moe import dropless_moe_ffn

__all__ = ["rmsnorm", "rope", "yarn_inv_freq", "dense_causal_attention",
           "gated_causal_attention", "dense_ffn", "routed_ffn",
           "seeded_tree", "hc_shapes", "hc_coefficients", "hc_read",
           "hc_write"]


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


def yarn_inv_freq(f, theta, factor, original, beta_fast, beta_slow):
    """YaRN's blend of a table f [d/2] of plain frequencies theta^(-2i/d),
    as transformers' `_compute_yarn_parameters`: frequencies that turn
    fewer than `beta_slow` times over the `original` context are divided
    by `factor`, those that turn more than `beta_fast` times are kept, a
    linear ramp between."""
    d = 2 * f.shape[0]

    def turns_at(n):        # the index whose frequency turns n times
        return d * math.log(original / (n * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


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


def _gate_flash(b, hq, hkv, s, d, dtype, window, scale, xla):
    """(key, candidates, make_args) for ops/autobench: the banded /
    grouped flash call against the caller's XLA spelling, each kind under
    a key of its own (a spelling other than `dense_causal_attention` is
    named in the key: the draw is between the kernel and THAT). An XLA
    candidate whose scores do not fit never wins (its error is kept in
    `perf.kernels()`)."""
    from ..ops.pallas_attention import flash_attention
    dtype = jnp.dtype(dtype)
    key = ("flash_band_gqa" if window is not None else "flash_full_gqa",
           b, hq, hkv, s, d, str(dtype), window)
    if xla is not dense_causal_attention:
        key += (xla.__name__,)

    def make_args():
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        return tuple(jax.random.normal(k, (b, s, h, d), jnp.float32)
                     .astype(dtype) for k, h in zip(keys, (hq, hkv, hkv)))

    def pallas(q, k, v):
        t = lambda a: a.transpose(0, 2, 1, 3)
        return t(flash_attention(t(q), t(k), t(v), scale=scale, causal=True,
                                 window=window))

    return key, {"pallas": pallas,
                 "xla": lambda q, k, v: xla(q, k, v, scale, window)}, make_args


def gated_causal_attention(q, k, v, scale, window, kernel: bool,
                           xla=dense_causal_attention):
    """q [B, T, H, d], k and v [B, T, Hkv, d] -> [B, T, H d]: causal, and
    inside `window` positions where given. With `kernel` the Pallas flash
    kernel (ops/pallas_attention.py: a band and grouped heads in its three
    calls, K and V never repeated) where its blocks divide T and, on a
    TPU, where the gate measures it faster than `xla(q, k, v, scale,
    window)`, the caller's XLA spelling of the same sum, at this shape;
    `xla` everywhere else. A window that every position of T lies inside
    is no band: both spellings then compute the triangle, under the
    triangle's key."""
    B, T, H, d = q.shape
    if window is not None and window >= T:
        window = None
    if kernel:
        from ..ops.pallas_attention import (_auto_block_k, _auto_block_q,
                                            on_tpu)
        if _auto_block_q(T) is not None and _auto_block_k(T) is not None:
            from ..ops import autobench
            key, cands, make_args = _gate_flash(
                B, H, k.shape[2], T, d, q.dtype, window, scale, xla)
            # interpreted off a TPU: no clock to ask
            if not on_tpu() or autobench.prefer(
                    key, cands, make_args, default="pallas") == "pallas":
                return cands["pallas"](q, k, v).reshape(B, T, H * d)
    return xla(q, k, v, scale, window)


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


# ---------------------------------------------------------------------------
# a residual that is not a sum: n streams mixed by hyper-connections whose
# carry-over matrix is made doubly stochastic (manifold-constrained,
# arXiv:2512.24880 over arXiv:2409.19606). A sub-layer's three steps:
#
#     pre, post, res = hc_coefficients(p, X, ...)   # from the streams
#     h  = hc_read(X, pre)                          # the branch's input
#     X' = hc_write(X, res, post, F(h))             # carry over and write
#
# The streams are a TUPLE of n arrays X_j [..., C], never one array: an
# axis of n = 4 before C would be the second-minor one, which a TPU pads
# to the 16 rows of a bfloat16 tile (four times the bytes), and stacked on
# any other axis every write ends in a concatenation that copies all n
# streams once more (half of the write's time by the TPU compiler's own
# cost model; scripts/hyper_step0.py). The coefficients carry their stream
# axes first, pre [n, ...], res [n, n, ...]: through the Sinkhorn loop the
# token axis is the minor one.
# ---------------------------------------------------------------------------

def hc_shapes(n: int, C: int) -> dict:
    """Parameters of one sub-layer's connections: `phi` [n C, n (n + 2)]
    (rows j C .. (j + 1) C meet stream j; columns [pre n | post n | res n n
    (row-major)]), `hc_bias` the same n (n + 2), `hc_scale` [3] = (a_pre,
    a_post, a_res)."""
    return {"phi": (n * C, n * (n + 2)), "hc_bias": (n * (n + 2),),
            "hc_scale": (3,)}


def hc_coefficients(p, X, iters: int, eps: float, clamp):
    """X (n arrays [..., C]) -> (pre [n, ...] = sigmoid, post [n, ...] = 2
    sigmoid, res [n, n, ...] doubly stochastic after `iters` Sinkhorn
    iterations: every column divided by its sum + eps, then every row), all
    float32.
    The norm over all n C numbers has no gain and scales a token's whole
    row, so it is applied to the n (n + 2) products and not to X."""
    n, C = len(X), X[0].shape[-1]
    r = jax.lax.rsqrt(sum(jnp.mean(jnp.square(x.astype(jnp.float32)),
                                   axis=-1) for x in X) / n + eps)
    phi = p["phi"].astype(X[0].dtype).reshape(n, C, -1)
    z = sum(jnp.einsum("...c,ck->...k", X[j], phi[j],
                       preferred_element_type=jnp.float32)
            for j in range(n))
    z = jnp.moveaxis(z, -1, 0) * r                      # [n (n + 2), ...]
    # a_pre, a_post, a_res, each over its own columns
    a = p["hc_scale"].astype(jnp.float32)[
        np.repeat(np.arange(3), (n, n, n * n))]
    tail = (1,) * r.ndim
    z = z * a.reshape((-1,) + tail) \
        + p["hc_bias"].astype(jnp.float32).reshape((-1,) + tail)
    pre = jax.nn.sigmoid(z[:n])
    post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
    m = jnp.exp(jnp.clip(z[2 * n:], clamp[0], clamp[1]))

    # the loop carries n n arrays of the tokens' shape, every sum spelled
    # as adds: elementwise all through (a `jnp.sum` over a stream axis of
    # [n, n, ...] whole made a reduction and a division of each half step,
    # four launches an iteration on a TPU v5e). A `fori_loop`, not 20
    # copies of the body: a twentieth of the program to compile
    # (scripts/hyper_step0.py has both readings)
    def step(_, m):
        cols = [sum(m[n * i + j] for i in range(n)) + eps for j in range(n)]
        m = [m[n * i + j] / cols[j] for i in range(n) for j in range(n)]
        rows = [sum(m[n * i:n * i + n]) + eps for i in range(n)]
        return tuple(m[n * i + j] / rows[i]
                     for i in range(n) for j in range(n))

    m = jax.lax.fori_loop(0, iters, step, tuple(m))      # m[n row + col]
    return pre, post, jnp.stack(m).reshape((n, n) + r.shape)


def hc_read(X, pre):
    """h [..., C] = sum_j pre_j X_j, in X's dtype."""
    h = sum(pre[j][..., None] * x.astype(jnp.float32)
            for j, x in enumerate(X))
    return h.astype(X[0].dtype)


def hc_write(X, res, post, f):
    """X' (n arrays): stream i = sum_j res_ij X_j + post_i f."""
    n = len(X)
    xs = [x.astype(jnp.float32) for x in X]
    ff = f.astype(jnp.float32)
    return tuple(
        (sum(res[i, j][..., None] * xs[j] for j in range(n))
         + post[i][..., None] * ff).astype(X[0].dtype) for i in range(n))


def seeded_tree(shapes, key, std: float, dtype):
    """A tree of seeded random leaves for a tree of shapes: matrices normal
    of `std`; a leaf named `*norm` a gain 1 + 0.1 normal (round one, not AT
    one: a dropped gain then shows); a leaf named `bias` normal of std 0.1
    (a program that weighs by score plus bias, or selects on the score,
    then disagrees); a leaf named `hc_bias` normal of std 1 and one named
    `hc_scale` 0.5 + 0.05 normal (hyper-connections far from their
    published start of a near-constant mix: a dropped term or a Sinkhorn
    loop cut short then shows). models/afmoe.py draws its weights the
    same way."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
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

"""DeepSeek-V3 block, functional core (`model_type: deepseek_v3`; defaults:
Kakao kanana-2-30b-a3b, the block without the query's low-rank step):
multi-head LATENT attention and, after `first_k_dense_replace` dense
layers, routed experts beside shared ones. Three keys a configuration may
set beside kanana's (`model_type: xing4_0` sets all three) are at the end
of this text.

    x = E[ids]
    for l in layers:
        h = rmsnorm(x, g1)
        q = h Wq -> [T, H, dn + dr] = [q_nope | q_rope]
        [c | kr] = h Wkva -> [T, C + dr];  c = rmsnorm(c, gkv)
        q_rope, kr = rope(q_rope), rope(kr)       # pairs (2i, 2i+1); kr is ONE
                                                  # key, shared by every head
        k_nope = c WK_h, v = c WV_h               # [T, H, dn], [T, H, dv]
        a = softmax((q_nope . k_nope + q_rope . kr) / sqrt(dn + dr), causal) v
        x = x + a Wo
        h2 = rmsnorm(x, g2)
        l < first_k_dense_replace:  f = swiglu(h2; F)
        else:  s = sigmoid(h2 Wg) (float32);  sel = top_k(s + b)
               w = scaling * s[sel] / sum(s[sel])
               f = sum_j w_j swiglu_{sel_j}(h2; Fm) + swiglu_shared(h2; n_shared Fm)
        x = x + f
    logits = rmsnorm(x, g_f) W_out                # untied

What a token leaves behind in a layer is the row [c | kr]: C + dr numbers
(576), not the H (dn + dr + dv) (10,240) of the keys and values it stands
for. Attention therefore has TWO forms of one function:

  expanded  the equations above: k_nope and v built from c for every head,
            products dn + dr wide. Cheapest over T^2 pairs: `forward` and
            the serving prefill.
  absorbed  WK_h folded into the query, WV_h applied after the sum:
            q'_h = q_nope_h WK_h^T                          [H, C]
            score = (q'_h . c_j + q_rope_h . kr_j) / sqrt(dn + dr)
            o_h = sum_j softmax_j(score) c_j                [H, C]
            a_h = o_h WV_h
            A query of C + dr against the cached row, the value the row's
            own first C numbers: nothing is expanded. The serving decode
            (`absorb_query`, `expand_value` round the paged kernel).

With `q_lora_rank` the query takes a low-rank step, q = rmsnorm(h Wq_a,
gq) Wq_b. With `rope_scaling` (YaRN) both rotations read the blended
frequency table (`layers.yarn_inv_freq`), cos and sin times mscale(factor,
mscale) / mscale(factor, mscale_all_dim), and the scores' scale carries
mscale(factor, mscale_all_dim)^2, in BOTH forms (`softmax_scale`; a YaRN
dict without both keys, or with an `attention_factor`, is refused). With
`hc_mult` n the residual is n streams, a tuple of n arrays [B, T, D]:
every sub-layer reads a mix of them and writes to all, x = hc_write(x,
res, post, F(hc_read(x, pre))) in place of x = x + F(x)
(`layers.hc_coefficients`, `hc_read`, `hc_write`; the embedding is copied
into every stream, and the streams are summed before the final norm).

The layer is written once (`apply_layers`, an unrolled loop: the first
layer's feed-forward is of another kind) and takes `attend(p, q_nope,
q_rope, c, kr, state, l) -> (a [B, T, H dv], state)`: what is kept of
[c | kr] and which form runs. `rmsnorm`, `dense_ffn` and the routed part
(`routed_ffn` -> parallel/moe.py::dropless_moe_ffn) are models/layers.py's;
the shared experts are one SwiGLU of n_shared x Fm that every token takes,
added beside the routed part there (`dropless_moe_ffn(shared=...)`).

Weights: `{"embed" [V, D], "head" [D, V], "norm" [D], "layers": [per layer
{"input_layernorm", "post_attention_layernorm" [D], "attn": {wq [D, H (dn +
dr)], wkv_a [D, C + dr], kv_a_layernorm [C], wk_b [C, H, dn], wv_b [C, H,
dv], wo [H dv, D]}, "ffn": {w1, w3 [D, F], w2 [F, D]} | {wg [D, E], bias
[E], w1, w3 [E, D, Fm], w2 [E, Fm, D], "shared": {w1, w3 [D, n Fm], w2
[n Fm, D]}}}]}`. `wk_b` / `wv_b` are a checkpoint's `kv_b_proj` split by
head into its key and value halves: the absorbed form reads each alone.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .layers import (dense_ffn, hc_coefficients, hc_read, hc_shapes,
                     hc_write, rmsnorm, routed_ffn, seeded_tree,
                     yarn_inv_freq)

__all__ = ["DeepseekV3Config", "init_params", "forward", "apply_layers",
           "expanded_attention", "absorb_query", "expand_value",
           "rope_pairs", "head_logits"]

_ROW_BLOCK = 512        # query rows at a time off the flash kernel
_FFN_ROW_BLOCK = 8192   # positions of the streams' feed-forward sub-layer


@dataclass(frozen=True)
class DeepseekV3Config:
    """The published keys of `config.json` (defaults: kanana-2-30b-a3b)."""
    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 1
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    q_lora_rank: int | None = None
    # YaRN, as `config.json` spells it (a dict; kept as sorted pairs)
    rope_scaling: tuple | None = None
    # residual streams mixed by hyper-connections (None: one, summed)
    hc_mult: int | None = None
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 32768
    initializer_range: float = 0.02
    dtype: str = "float32"
    # global ids of the routed experts held here (None = all), as lfm2's
    experts_held: tuple | None = None

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        if self.rope_scaling is not None and self.yarn.get("type") != "yarn":
            raise NotImplementedError(
                f"rope_scaling {self.yarn}: only YaRN is built")
        if self.rope_scaling is not None and (
                "attention_factor" in self.yarn or not (
                    self.yarn.get("mscale")
                    and self.yarn.get("mscale_all_dim"))):
            raise NotImplementedError(
                f"rope_scaling {self.yarn}: YaRN is built with `mscale` "
                f"and `mscale_all_dim` both set and no `attention_factor` "
                f"(without them transformers scales cos and sin by 0.1 "
                f"ln(factor) + 1)")
        if (self.n_group, self.topk_group) != (1, 1):
            raise NotImplementedError(
                f"n_group {self.n_group}, topk_group {self.topk_group}: "
                f"only one group is built (the group-limited step then "
                f"keeps the only group)")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (pairs)")

    # -- what the shared sub-layers of models/layers.py read ----------------
    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    use_expert_bias = True

    @property
    def num_moe_layers(self) -> int:
        return max(0, self.num_hidden_layers - self.first_k_dense_replace)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Numbers a token leaves in a layer: [c | kr]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def yarn(self) -> dict:
        return dict(self.rope_scaling or ())

    @property
    def softmax_scale(self) -> float:
        """Of the scores, in the expanded and the absorbed form alike:
        1 / sqrt(dn + dr), times YaRN's mscale(factor, mscale_all_dim)^2."""
        scale = 1.0 / math.sqrt(self.qk_head_dim)
        if self.yarn:
            scale *= _mscale(self.yarn["factor"],
                             self.yarn["mscale_all_dim"]) ** 2
        return scale

    @classmethod
    def tiny(cls, **kw):
        """Every kind of layer at test size: one dense layer, two expert
        layers of 8 experts (2 a token) beside 2 shared."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                    moe_intermediate_size=32, num_hidden_layers=3,
                    n_routed_experts=8, num_experts_per_tok=2,
                    num_attention_heads=4, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=24,
                    max_position_embeddings=512, initializer_range=0.1)
        base.update(kw)
        return cls(**base)


def _mscale(factor: float, mscale: float) -> float:
    """DeepSeek's `yarn_get_mscale`."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def layer_shapes(cfg: DeepseekV3Config, l: int) -> dict:
    D, H, C = cfg.hidden_size, cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    out = {"input_layernorm": (D,), "post_attention_layernorm": (D,),
           "attn": {"wq": (D, H * (dn + dr)), "wkv_a": (D, C + dr),
                    "kv_a_layernorm": (C,), "wk_b": (C, H, dn),
                    "wv_b": (C, H, dv), "wo": (H * dv, D)}}
    if cfg.q_lora_rank is not None:
        r = cfg.q_lora_rank
        del out["attn"]["wq"]
        out["attn"].update(wq_a=(D, r), q_a_layernorm=(r,),
                           wq_b=(r, H * (dn + dr)))
    if cfg.hc_mult:
        out["hc_attn"] = hc_shapes(cfg.hc_mult, D)
        out["hc_ffn"] = hc_shapes(cfg.hc_mult, D)
    if l < cfg.first_k_dense_replace:
        F = cfg.intermediate_size
        out["ffn"] = {"w1": (D, F), "w3": (D, F), "w2": (F, D)}
    else:
        E, F = cfg.n_routed_experts, cfg.moe_intermediate_size
        Eh = E if cfg.experts_held is None else len(cfg.experts_held)
        Fs = cfg.n_shared_experts * F
        out["ffn"] = {"wg": (D, E), "bias": (E,), "w1": (Eh, D, F),
                      "w3": (Eh, D, F), "w2": (Eh, F, D),
                      "shared": {"w1": (D, Fs), "w3": (D, Fs),
                                 "w2": (Fs, D)}}
    return out


def init_params(cfg: DeepseekV3Config, seed: int = 0):
    """Seeded random weights (`seeded_tree`), a key a layer."""
    dtype = jnp.dtype(cfg.dtype)
    key = jax.random.PRNGKey(seed)
    top = seeded_tree({"embed": (cfg.vocab_size, cfg.hidden_size),
                       "head": (cfg.hidden_size, cfg.vocab_size),
                       "norm": (cfg.hidden_size,)},
                      jax.random.fold_in(key, 10_000),
                      cfg.initializer_range, dtype)
    top["layers"] = [seeded_tree(layer_shapes(cfg, l),
                                 jax.random.fold_in(key, l),
                                 cfg.initializer_range, dtype)
                     for l in range(cfg.num_hidden_layers)]
    return top


# ---------------------------------------------------------------------------
# sub-layers, each written once
# ---------------------------------------------------------------------------

def rope_pairs(x, positions, theta, yarn=None):
    """RoPE over adjacent pairs (2i, 2i+1) of the last axis
    (`rope_interleave`). x [B, T, ..., d], positions [B, T]. `yarn`
    (`rope_scaling`): the blended table, cos and sin times mscale(factor,
    mscale) / mscale(factor, mscale_all_dim)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if yarn:
        inv = yarn_inv_freq(
            inv, theta, yarn["factor"],
            yarn["original_max_position_embeddings"],
            yarn.get("beta_fast", 32), yarn.get("beta_slow", 1))
    ang = positions.astype(jnp.float32)[..., None] * inv      # [B, T, d/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn:
        factor = _mscale(yarn["factor"], yarn["mscale"]) \
            / _mscale(yarn["factor"], yarn["mscale_all_dim"])
        if factor != 1.0:
            cos, sin = cos * factor, sin * factor
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def low_rank_query(p, h, eps):
    """The query with `q_lora_rank`: rmsnorm(h Wq_a, gq) Wq_b."""
    return rmsnorm(h @ p["wq_a"], p["q_a_layernorm"], eps) @ p["wq_b"]


def latent_projections(p, h, positions, cfg):
    """h [B, T, D] -> (q_nope [B, T, H, dn], q_rope [B, T, H, dr] rotated,
    c [B, T, C] normed, kr [B, T, dr] rotated): the query, and the row a
    token leaves behind."""
    B, T, _ = h.shape
    H, dn, C = cfg.num_attention_heads, cfg.qk_nope_head_dim, \
        cfg.kv_lora_rank
    q = h @ p["wq"] if cfg.q_lora_rank is None \
        else low_rank_query(p, h, cfg.rms_norm_eps)
    q = q.reshape(B, T, H, cfg.qk_head_dim)
    ckr = h @ p["wkv_a"]
    c = rmsnorm(ckr[..., :C], p["kv_a_layernorm"], cfg.rms_norm_eps)
    rot = (cfg.rope_theta, cfg.yarn)
    return (q[..., :dn], rope_pairs(q[..., dn:], positions, *rot),
            c, rope_pairs(ckr[..., C:], positions, *rot))


def _causal_rows(q, k, v, scale, row0):
    """Rows row0 .. of q [B, R, H, d] against all of k [B, T, H, d] and v
    [B, T, H, dv], causal."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    qi = row0 + jnp.arange(q.shape[1])[:, None]
    s = jnp.where(jnp.arange(k.shape[1])[None, :] <= qi, s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", pr.astype(v.dtype), v)


def causal_attention(q, k, v, scale):
    """q, k [B, T, H, d], v [B, T, H, dv] (another width) -> [B, T, H dv].
    On a TPU the flash kernel (its forward takes a value width of its
    own); elsewhere plain softmax, the query rows in blocks once the
    [H, T, T] scores would not fit (537 MB of float32 at 2,048, 8.6 GB at
    8,192)."""
    from ..ops.pallas_attention import can_use_flash, flash_attention
    B, T, H, _ = q.shape
    heads_first = [a.transpose(0, 2, 1, 3) for a in (q, k, v)]
    if can_use_flash(*heads_first, None):
        o = flash_attention(
            *heads_first, scale=scale, causal=True,
            # whole K and V of a head sit in VMEM: at 8,192 positions a
            # query block of 512 beside them passes Mosaic's 16 MiB
            block_q=256 if T > 4096 else None)
        return o.transpose(0, 2, 1, 3).reshape(B, T, -1)
    if T <= 2 * _ROW_BLOCK or T % _ROW_BLOCK:
        return _causal_rows(q, k, v, scale, 0).reshape(B, T, -1)
    qb = q.reshape(B, T // _ROW_BLOCK, _ROW_BLOCK, H, -1).swapaxes(0, 1)
    rows = jnp.arange(T // _ROW_BLOCK) * _ROW_BLOCK
    o = jax.lax.map(lambda a: _causal_rows(a[0], k, v, scale, a[1]),
                    (qb, rows))
    return o.swapaxes(0, 1).reshape(B, T, -1)


def expanded_attention(p, q_nope, q_rope, c, kr, scale):
    """The expanded form over one whole causal sequence: keys and values
    of every head built from c. Returns [B, T, H dv]."""
    H = q_nope.shape[2]
    k_nope = jnp.einsum("btc,chd->bthd", c, p["wk_b"])
    v = jnp.einsum("btc,chd->bthd", c, p["wv_b"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr[:, :, None, :],
                                  kr.shape[:2] + (H, kr.shape[-1]))], -1)
    return causal_attention(jnp.concatenate([q_nope, q_rope], -1), k, v,
                            scale)


def absorb_query(p, q_nope, q_rope):
    """The absorbed form's query [..., H, C + dr] = [q_nope WK^T | q_rope]:
    what meets a cached row [c | kr]."""
    qc = jnp.einsum("...hd,chd->...hc", q_nope, p["wk_b"])
    return jnp.concatenate([qc, q_rope.astype(qc.dtype)], -1)


def expand_value(p, o):
    """The absorbed form's output o [..., H, C] (a softmax-weighted sum of
    cached c) through the value up-projection: [..., H dv]."""
    a = jnp.einsum("...hc,chd->...hd", o, p["wv_b"])
    return a.reshape(a.shape[:-2] + (-1,))


def head_logits(params, x, cfg):
    """Final norm and the untied head, float32 logits."""
    x = rmsnorm(x, params["norm"], cfg.rms_norm_eps)
    return jnp.einsum("...d,dv->...v", x, params["head"],
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# the one loop over the layers, and the plain driver
# ---------------------------------------------------------------------------

def residual(cfg, hc, x, branch):
    """x through one sub-layer, `branch(h) -> (f, aux)`: x + f, or with
    `hc_mult` streams (x a tuple of them, `hc` the sub-layer's
    connections) what the branch makes of a mix of them, written to all of
    them beside their doubly-stochastic carry-over. Returns (x', aux)."""
    if not cfg.hc_mult:
        f, aux = branch(x)
        return x + f, aux
    pre, post, res = hc_coefficients(
        hc, x, cfg.hc_sinkhorn_iters, cfg.hc_eps,
        (cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max))
    f, aux = branch(hc_read(x, pre))
    return hc_write(x, res, post, f), aux


def _in_row_blocks(fn, x):
    """`fn(x) -> (x', sel)` over the streams x (a tuple of [B, T, D]) in
    equal blocks of at most `_FFN_ROW_BLOCK` positions, one after another
    (a bucket up to that size is one block, and the compiler drops a loop
    of one turn): a sub-layer in which no position meets another (the
    feed-forward one with its two mixes) then holds a block's temporaries,
    not a bucket's (at 16,384 positions the experts' sorted rows, their
    products and the float32 sum over the k choices are 2 GiB beside four
    streams)."""
    B, T, D = x[0].shape
    nb = -(-T // _FFN_ROW_BLOCK)
    while T % nb:
        nb += 1
    blocks = tuple(a.reshape(B, nb, T // nb, D).swapaxes(0, 1) for a in x)
    out, sel = jax.lax.map(fn, blocks)
    if sel is not None:         # [nb, B block, k] -> [B T, k]
        sel = sel.reshape(nb, B, T // nb, -1).swapaxes(0, 1) \
            .reshape(B * T, -1)
    return tuple(a.swapaxes(0, 1).reshape(B, T, D) for a in out), sel


def apply_layers(cfg, params, x, positions, attend, state):
    """x [B, T, D] through every layer. `attend(p["attn"], q_nope, q_rope,
    c, kr, state, l) -> (a [B, T, H dv], state)`. Returns (x, state, sel
    [expert layers, B T, k])."""
    sels = []
    eps = cfg.rms_norm_eps
    if cfg.hc_mult:
        x = (x,) * cfg.hc_mult
    for l, p in enumerate(params["layers"]):
        def attention(x, p=p, l=l):
            nonlocal state
            h = rmsnorm(x, p["input_layernorm"], eps)
            a, state = attend(p["attn"], *latent_projections(
                p["attn"], h, positions, cfg), state, l)
            return a @ p["attn"]["wo"], None

        def feed_forward(x, p=p, l=l):
            h = rmsnorm(x, p["post_attention_layernorm"], eps)
            if l < cfg.first_k_dense_replace:
                return dense_ffn(p["ffn"], h), None
            return routed_ffn(p["ffn"], h, cfg)

        def ffn_sub_layer(x, p=p, feed_forward=feed_forward):
            return residual(cfg, p.get("hc_ffn"), x, feed_forward)

        x, _ = residual(cfg, p.get("hc_attn"), x, attention)
        x, sel = _in_row_blocks(ffn_sub_layer, x) if cfg.hc_mult \
            else ffn_sub_layer(x)
        if sel is not None:
            sels.append(sel)
    if cfg.hc_mult:
        x = sum(x)
    k = cfg.num_experts_per_tok
    sel = jnp.stack(sels) if sels else jnp.zeros(
        (0, x.shape[0] * x.shape[1], k), jnp.int32)
    return x, state, sel


def forward(params, ids, cfg: DeepseekV3Config):
    """ids [B, T] -> logits [B, T, V] float32: the whole sequence at once,
    no cache, the expanded form."""
    B, T = ids.shape
    x = jnp.take(params["embed"], ids, axis=0)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def attend(p, q_nope, q_rope, c, kr, state, l):
        return expanded_attention(p, q_nope, q_rope, c, kr,
                                  cfg.softmax_scale), state

    x, _, _ = apply_layers(cfg, params, x, positions, attend, None)
    return head_logits(params, x, cfg)

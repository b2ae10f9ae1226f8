"""AFMoE block, functional core (`model_type: afmoe`; defaults: Arcee
Trinity-Mini, 26B-A3B): grouped-query attention of TWO kinds in one model
(`layer_types`: most layers attend to the last `sliding_window` positions
and carry RoPE, every `global_attn_every_n_layers`-th attends to the whole
context and carries no positions at all), a gated attention output, four
norms a layer, and after `num_dense_layers` dense layers routed experts
beside a shared one.

    x = E[ids] * sqrt(D)                          # mup_enabled
    for l in layers:
        a = rmsnorm(x, g_in)
        q = a Wq -> [T, H, d];  k = a Wk, v = a Wv -> [T, Hkv, d];  g = a Wg
        q = rmsnorm(q, gq), k = rmsnorm(k, gk)    # over a head's d
        sliding_attention:  q, k = rope(q), rope(k)   # rotate-half, theta
        o_i = sum_j softmax_j(q_i . k_j / sqrt(d)) v_j   over j <= i, and on
              a sliding layer i - j < sliding_window; KV head h serves query
              heads G h .. G h + G - 1
        x = x + rmsnorm((o * sigmoid(g)) Wo, g_post_attn)
        m = rmsnorm(x, g_pre_mlp)
        l < num_dense_layers:  f = swiglu(m; F)
        else:  s = sigmoid(m Wr) (float32);  sel = top_k(s + b)
               w = route_scale * s[sel] / sum(s[sel])
               f = sum_j w_j swiglu_{sel_j}(m; Fm) + swiglu_shared(m; n Fm)
        x = x + rmsnorm(f, g_post_mlp)
    logits = rmsnorm(x, g_f) W_out                # untied

What a system keeps of a token differs by the layer's kind: a full layer
needs K and V of every cached position, a sliding layer those of the last
`sliding_window` only. So the layer is written once (`apply_layers`, an
unrolled loop: kinds and feed-forwards differ) and takes `attend(q, k, v,
state, l) -> (o [B, T, H d], state)`: what is kept of k and v and what q
attends over, by `cfg.layer_types[l]`. `rmsnorm`, the rotate-half RoPE,
`dense_ffn` and the routed part (`routed_ffn` -> parallel/moe.py::
dropless_moe_ffn, the shared expert added beside it there) are
models/layers.py's.

Weights: `{"embed" [V, D], "head" [D, V], "norm" [D], "layers": [per layer
{"input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm",
"post_mlp_layernorm" [D], "attn": {wq, w_gate [D, H d], wk, wv [D, Hkv d],
wo [H d, D], q_norm, k_norm [d]}, "ffn": {w1, w3 [D, F], w2 [F, D]} | {wg
[D, E], bias [E], w1, w3 [E, D, Fm], w2 [E, Fm, D], "shared": {w1, w3 [D,
n Fm], w2 [n Fm, D]}}}]}`.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .layers import _rope, dense_ffn, rmsnorm, routed_ffn, seeded_tree

__all__ = ["AfmoeConfig", "SLIDING", "FULL", "init_params", "forward",
           "apply_layers", "banded_causal_attention", "window_of",
           "head_logits", "embed_tokens"]

SLIDING, FULL = "sliding_attention", "full_attention"
_ROW_BLOCK = 512        # query rows at a time
_SCORE_ELEMENTS = 2 ** 22   # rows x keys of one block's scores, a head


@dataclass(frozen=True)
class AfmoeConfig:
    """The published keys of `config.json` (defaults: Trinity-Mini)."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    sliding_window: int = 2048
    global_attn_every_n_layers: int = 4
    # None: sliding but every `global_attn_every_n_layers`-th
    layer_types: tuple | None = None
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    route_norm: bool = True
    route_scale: float = 2.826
    score_func: str = "sigmoid"
    mup_enabled: bool = True
    n_group: int = 1
    topk_group: int = 1
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    dtype: str = "float32"
    # global ids of the routed experts held here (None = all), as lfm2's
    experts_held: tuple | None = None

    def __post_init__(self):
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            object.__setattr__(self, "layer_types", tuple(
                FULL if (l + 1) % n == 0 else SLIDING
                for l in range(self.num_hidden_layers)))
        else:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {self.layer_types} for "
                             f"{self.num_hidden_layers} layers")
        if self.score_func != "sigmoid":
            raise NotImplementedError(
                f"score_func {self.score_func!r}: only the sigmoid router "
                f"is built")
        if (self.n_group, self.topk_group) != (1, 1):
            raise NotImplementedError(
                f"n_group {self.n_group}, topk_group {self.topk_group}: "
                f"only one group is built")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are not whole groups of KV heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even (rotate-half)")

    # -- what the shared sub-layers of models/layers.py read ----------------
    use_expert_bias = True

    @property
    def norm_topk_prob(self) -> bool:
        return self.route_norm

    @property
    def routed_scaling_factor(self) -> float:
        return self.route_scale

    @property
    def num_moe_layers(self) -> int:
        return max(0, self.num_hidden_layers - self.num_dense_layers)

    def layers_of(self, kind: str) -> int:
        return sum(1 for k in self.layer_types if k == kind)

    def index_in_kind(self, l: int) -> int:
        """Which of its kind's layers layer l is (a cache stacks a kind's
        layers)."""
        return sum(1 for k in self.layer_types[:l]
                   if k == self.layer_types[l])

    @property
    def embed_scale(self) -> float:
        return math.sqrt(self.hidden_size) if self.mup_enabled else 1.0

    @classmethod
    def tiny(cls, **kw):
        """Every kind of layer at test size: two dense layers, then expert
        layers of 8 experts (2 a token) beside a shared one; sliding,
        sliding, sliding, full, sliding; a window of 8."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=160,
                    moe_intermediate_size=32, num_hidden_layers=5,
                    num_dense_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, num_experts=8,
                    num_experts_per_tok=2, sliding_window=8,
                    max_position_embeddings=512, initializer_range=0.1)
        base.update(kw)
        return cls(**base)


def layer_shapes(cfg: AfmoeConfig, l: int) -> dict:
    D, d = cfg.hidden_size, cfg.head_dim
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    out = {"input_layernorm": (D,), "post_attention_layernorm": (D,),
           "pre_mlp_layernorm": (D,), "post_mlp_layernorm": (D,),
           "attn": {"wq": (D, H * d), "wk": (D, Hkv * d), "wv": (D, Hkv * d),
                    "w_gate": (D, H * d), "wo": (H * d, D),
                    "q_norm": (d,), "k_norm": (d,)}}
    if l < cfg.num_dense_layers:
        F = cfg.intermediate_size
        out["ffn"] = {"w1": (D, F), "w3": (D, F), "w2": (F, D)}
    else:
        E, F = cfg.num_experts, cfg.moe_intermediate_size
        Eh = E if cfg.experts_held is None else len(cfg.experts_held)
        Fs = cfg.num_shared_experts * F
        out["ffn"] = {"wg": (D, E), "bias": (E,), "w1": (Eh, D, F),
                      "w3": (Eh, D, F), "w2": (Eh, F, D),
                      "shared": {"w1": (D, Fs), "w3": (D, Fs),
                                 "w2": (Fs, D)}}
    return out


def init_params(cfg: AfmoeConfig, seed: int = 0):
    """Seeded random weights, drawn as models/deepseek_v3.py's
    (`layers.seeded_tree`: gains 1 + 0.1 normal, the experts' bias of std 0.1)."""
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

def _band_rows(q, k, v, scale, row0, key0, window):
    """Rows row0 .. of q [B, R, H, d] against the keys key0 .. of k, v
    [B, K, Hkv, d]: causal, and within `window` positions where given."""
    B, R, H, d = q.shape
    Hkv = k.shape[2]
    s = jnp.einsum("bqkgd,bskd->bkgqs", q.reshape(B, R, Hkv, H // Hkv, d),
                   k, preferred_element_type=jnp.float32) * scale
    qi = row0 + jnp.arange(R, dtype=jnp.int32)[:, None]
    kj = key0 + jnp.arange(k.shape[1], dtype=jnp.int32)[None, :]
    ok = kj <= qi
    if window is not None:
        ok = jnp.logical_and(ok, qi - kj < window)
    pr = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", pr.astype(v.dtype),
                      v).reshape(B, R, H * d)


def banded_causal_attention(q, k, v, scale, window=None,
                            row_block: int = _ROW_BLOCK):
    """q [B, T, H, d], k and v [B, T, Hkv, d] -> [B, T, H d]: position i
    attends to j <= i and, with `window`, to i - j < window. XLA's
    spelling: float32 scores, a mask, a softmax. The query rows go in
    blocks once a whole [T, T] of scores a head is too much; a block of a
    window layer meets the `window + block` keys that can reach it and no
    others (O(T window)), a block of a full layer all T (a prompt of
    16,384: 256 rows at a time, half a GiB of scores, three passes over
    them). What runs it: `forward` below (no cache: tests, the reference's
    counterpart), and the serving prefill (`serving/model.py::
    WindowedDecodeModel.prefill`) off a TPU, for a bucket the flash
    kernel's blocks do not divide, and as the gate's other candidate
    (`layers.gated_causal_attention`: on a TPU the kernel holds every
    bucket of 1,024 and over, an eighth of this function's time on the
    triangle at 16,384 and a quarter on a band: docs/KERNELS.md, "The
    serving prefill's two calls")."""
    B, T, H, d = q.shape
    R = min(row_block, T)
    if window is None or window + R >= T:
        span = T
        R = max(64, min(R, _SCORE_ELEMENTS // T))
    else:
        span = window + R
    if T <= R or T % R:
        return _band_rows(q, k, v, scale, 0, 0, window)
    qb = q.reshape(B, T // R, R, H, d).swapaxes(0, 1)
    rows = jnp.arange(T // R, dtype=jnp.int32) * R

    def block(a):
        qr, row0 = a
        if span == T:
            return _band_rows(qr, k, v, scale, row0, 0, window)
        key0 = jnp.clip(row0 + R - span, 0, T - span)
        return _band_rows(
            qr, jax.lax.dynamic_slice_in_dim(k, key0, span, 1),
            jax.lax.dynamic_slice_in_dim(v, key0, span, 1), scale, row0,
            key0, window)

    o = jax.lax.map(block, (qb, rows))
    return o.swapaxes(0, 1).reshape(B, T, H * d)


def attention_operator(p, h, positions, attend, state, cfg, l):
    """Layer l's attention on h [B, T, D]: q/k norms over a head, RoPE on
    a sliding layer and no positions on a full one, `attend(q, k, v, state,
    l) -> (o [B, T, H d], state)`, the output gated by sigmoid(h Wg)."""
    B, T, _ = h.shape
    d, H, Hkv = cfg.head_dim, cfg.num_attention_heads, \
        cfg.num_key_value_heads
    q = rmsnorm((h @ p["wq"]).reshape(B, T, H, d), p["q_norm"],
                cfg.rms_norm_eps)
    k = rmsnorm((h @ p["wk"]).reshape(B, T, Hkv, d), p["k_norm"],
                cfg.rms_norm_eps)
    v = (h @ p["wv"]).reshape(B, T, Hkv, d)
    if rotates(cfg, l):
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    o, state = attend(q, k, v, state, l)
    return (o * output_gate(p, h).astype(o.dtype)) @ p["wo"], state


def rotates(cfg, l: int) -> bool:
    """Whether layer l's q and k carry RoPE: a sliding layer's do, a full
    layer's carry no positions at all."""
    return cfg.layer_types[l] == SLIDING


def output_gate(p, h):
    """sigmoid(h Wg) [B, T, H d], float32: what the attention output is
    multiplied by, lane for lane, before the out-projection."""
    return jax.nn.sigmoid((h @ p["w_gate"]).astype(jnp.float32))


def embed_tokens(params, ids, cfg):
    """E[ids] * sqrt(D) (`mup_enabled`), in the weights' dtype."""
    x = jnp.take(params["embed"], ids, axis=0)
    return x * jnp.asarray(cfg.embed_scale, x.dtype)


def head_logits(params, x, cfg):
    """Final norm and the untied head, float32 logits."""
    x = rmsnorm(x, params["norm"], cfg.rms_norm_eps)
    return jnp.einsum("...d,dv->...v", x, params["head"],
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# the one loop over the layers, and the plain driver
# ---------------------------------------------------------------------------

def apply_layers(cfg, params, x, positions, attend, state):
    """x [B, T, D] through every layer; `attend` as `attention_operator`
    says. Returns (x, state, sel [expert layers, B T, k])."""
    sels = []
    eps = cfg.rms_norm_eps
    for l, p in enumerate(params["layers"]):
        h = rmsnorm(x, p["input_layernorm"], eps)
        a, state = attention_operator(p["attn"], h, positions, attend,
                                      state, cfg, l)
        x = x + rmsnorm(a, p["post_attention_layernorm"], eps)
        h = rmsnorm(x, p["pre_mlp_layernorm"], eps)
        if l < cfg.num_dense_layers:
            f = dense_ffn(p["ffn"], h)
        else:
            f, sel = routed_ffn(p["ffn"], h, cfg)
            sels.append(sel)
        x = x + rmsnorm(f, p["post_mlp_layernorm"], eps)
    k = cfg.num_experts_per_tok
    sel = jnp.stack(sels) if sels else jnp.zeros(
        (0, x.shape[0] * x.shape[1], k), jnp.int32)
    return x, state, sel


def window_of(cfg, l: int):
    """The window of layer l in positions, None for a full layer."""
    return cfg.sliding_window if cfg.layer_types[l] == SLIDING else None


def forward(params, ids, cfg: AfmoeConfig):
    """ids [B, T] -> logits [B, T, V] float32: the whole sequence at once,
    no cache, banded causal attention in row blocks."""
    B, T = ids.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    scale = 1.0 / math.sqrt(cfg.head_dim)

    def attend(q, k, v, state, l):
        return banded_causal_attention(q, k, v, scale,
                                       window_of(cfg, l)), state

    x, _, _ = apply_layers(cfg, params, embed_tokens(params, ids, cfg),
                           positions, attend, None)
    return head_logits(params, x, cfg)

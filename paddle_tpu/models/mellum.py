"""Mellum block, functional core (`model_type: mellum`; defaults: JetBrains
Mellum2-12B-A2.5B): grouped-query attention of two kinds in one model
(`layer_types`: three layers attend to the last `sliding_window` positions
under plain RoPE, every fourth to the whole context under YaRN-scaled
RoPE), q/k norms, two norms a layer, and in every layer dropless
softmax-routed experts with nothing shared and nothing dense.

    x = E[ids]
    for l in layers:
        a = rmsnorm(x, g1)
        q = a Wq -> [T, H, d];  k = a Wk, v = a Wv -> [T, Hkv, d]
        q = rmsnorm(q, gq), k = rmsnorm(k, gk)          # over a head's d
        q, k = rope(q), rope(k)      # sliding: theta^(-2i/d); full: YaRN's
                                     # blend, cos and sin times its factor
        o_i = sum_j softmax_j(q_i . k_j / sqrt(d)) v_j   over j <= i, and on
              a sliding layer i - j < sliding_window; KV head h serves query
              heads G h .. G h + G - 1
        x = x + o Wo
        m = rmsnorm(x, g2)
        p = softmax(m Wr) (float32);  sel = top_k(p);  w = p[sel] / sum(p[sel])
        x = x + sum_{e in sel, e held here} w_e swiglu_e(m)
    loss = mean next-token cross-entropy of rmsnorm(x, gf) W_out   # untied

This core is TRAINED (`parallel/hybrid.py::HybridParallelTrainStep` takes
a `MellumTrainModel`): one expert-parallel rank's share. `experts_held`
names the experts whose weights live here; the router still scores all E
and normalises over all it chose, and what the absent experts would add is
left out (the other ranks' parts add up to the whole layer:
tests/test_mellum_model.py). The vocabulary may be a slice of the
published one: the loss is over the rows held.

Weights, a layer's leaves stacked `[L, ...]` (every layer has one shape;
the kinds differ in what attention does, not in what it holds):
`{"embed" [V, D], "head" [D, V], "norm" [D], "layers":
{"input_layernorm", "post_attention_layernorm" [L, D], "attn": {wq [L, D,
H d], wk, wv [L, D, Hkv d], wo [L, H d, D], q_norm, k_norm [L, d]},
"ffn": {wg [L, D, E], w1, w3 [L, Eh, D, F], w2 [L, Eh, F, D]}}}`.
`rmsnorm`, `rope`, the attention (`gated_causal_attention`: the flash
kernel or XLA's scores) and `routed_ffn` (-> parallel/moe.py::
dropless_moe_ffn) are models/layers.py's.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import (gated_causal_attention, rmsnorm, rope, routed_ffn,
                     seeded_tree, yarn_inv_freq)

__all__ = ["MellumConfig", "MellumTrainModel", "SLIDING", "FULL",
           "init_params", "param_shapes", "rope_table", "loss_and_chosen",
           "attend"]

SLIDING, FULL = "sliding_attention", "full_attention"
DECAYED = frozenset({"embed", "head", "wq", "wk", "wv", "wo", "wg", "w1",
                     "w3", "w2"})


@dataclass(frozen=True)
class MellumConfig:
    """The published keys of `config.json` (defaults: Mellum2-12B-A2.5B)
    and how this program runs them."""
    vocab_size: int = 98304
    hidden_size: int = 2304
    moe_intermediate_size: int = 896
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    sliding_window: int = 1024
    # None: three sliding layers, then a full one
    layer_types: tuple | None = None
    rope_theta: float = 500000.0
    # YaRN on the full layers (rope_parameters.full_attention)
    yarn_factor: float = 16.0
    yarn_original_max_position_embeddings: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    # global ids of the experts held here (None = all)
    experts_held: tuple | None = None
    # float32 master weights; blocks compute in this dtype
    amp_dtype: str | None = "bfloat16"
    attn_impl: str = "flash"        # "flash" (Pallas, gated) | "xla"
    remat: bool = True              # each layer recomputed in backward

    def __post_init__(self):
        if self.layer_types is None:
            object.__setattr__(self, "layer_types", tuple(
                FULL if (l + 1) % 4 == 0 else SLIDING
                for l in range(self.num_hidden_layers)))
        else:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {self.layer_types} for "
                             f"{self.num_hidden_layers} layers")
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held",
                               tuple(int(e) for e in self.experts_held))
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are not whole groups of KV heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even (rotate-half)")
        if self.attn_impl not in ("flash", "xla"):
            raise ValueError(f"unknown attn_impl {self.attn_impl!r}")

    # -- what models/layers.py::routed_ffn reads ----------------------------
    use_expert_bias = False
    routed_scaling_factor = 1.0
    score_func = "softmax"

    @property
    def held(self) -> tuple:
        return self.experts_held if self.experts_held is not None \
            else tuple(range(self.num_experts))

    @classmethod
    def tiny(cls, **kw):
        """Both kinds of layer at test size: 8 experts, 2 a token, a
        window of 8."""
        base = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
                    num_hidden_layers=4, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, num_experts=8,
                    num_experts_per_tok=2, sliding_window=8,
                    yarn_original_max_position_embeddings=16,
                    max_position_embeddings=256, initializer_range=0.1,
                    amp_dtype=None, attn_impl="xla")
        base.update(kw)
        return cls(**base)


def param_shapes(cfg: MellumConfig) -> dict:
    D, d, L = cfg.hidden_size, cfg.head_dim, cfg.num_hidden_layers
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    E, Eh, F = cfg.num_experts, len(cfg.held), cfg.moe_intermediate_size
    return {"embed": (cfg.vocab_size, D), "head": (D, cfg.vocab_size),
            "norm": (D,),
            "layers": {
                "input_layernorm": (L, D),
                "post_attention_layernorm": (L, D),
                "attn": {"wq": (L, D, H * d), "wk": (L, D, Hkv * d),
                         "wv": (L, D, Hkv * d), "wo": (L, H * d, D),
                         "q_norm": (L, d), "k_norm": (L, d)},
                "ffn": {"wg": (L, D, E), "w1": (L, Eh, D, F),
                        "w3": (L, Eh, D, F), "w2": (L, Eh, F, D)}}}


def param_specs(cfg: MellumConfig) -> dict:
    """Every leaf whole on every device: one rank's share is what this
    core holds (the exchange between ranks is not built)."""
    return jax.tree_util.tree_map(lambda _s: P(), param_shapes(cfg),
                                  is_leaf=lambda s: isinstance(s, tuple))


def init_params(cfg: MellumConfig, seed: int = 0, out_shardings=None):
    """Seeded random float32 weights made on the device in one jitted
    call (`layers.seeded_tree`: matrices normal of `initializer_range`,
    gains 1 + 0.1 normal)."""
    kw = {} if out_shardings is None else {"out_shardings": out_shardings}
    make = jax.jit(lambda key: seeded_tree(
        param_shapes(cfg), key, cfg.initializer_range, jnp.float32), **kw)
    return make(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# sub-layers
# ---------------------------------------------------------------------------

def rope_table(cfg: MellumConfig, kind: str):
    """(inv_freq [d/2], factor) of a layer kind. Sliding: the plain
    theta^(-2i/d), factor 1. Full: YaRN's blend (`layers.yarn_inv_freq`);
    cos and sin both carry `attention_factor`."""
    d = cfg.head_dim
    f = cfg.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if kind == SLIDING:
        return f, 1.0
    return yarn_inv_freq(
        f, cfg.rope_theta, cfg.yarn_factor,
        cfg.yarn_original_max_position_embeddings, cfg.yarn_beta_fast,
        cfg.yarn_beta_slow), cfg.yarn_attention_factor


def attend(q, k, v, scale, window, impl):
    """q [B, T, H, d], k and v [B, T, Hkv, d] -> [B, T, H d]: causal, and
    inside `window` positions where given. "flash": the Pallas kernel
    where the gate measures it faster than XLA's whole [T, T] of scores,
    on a TPU (`layers.gated_causal_attention`, which the serving prefill
    of models/afmoe.py shares)."""
    return gated_causal_attention(q, k, v, scale, window, impl == "flash")


def layer(p, x, positions, cfg: MellumConfig, kind: str):
    """One layer on x [B, T, D] with its own (unstacked) weights p.
    Returns (x, sel [B T, k] the experts chosen)."""
    cdt = jnp.dtype(cfg.amp_dtype) if cfg.amp_dtype else x.dtype
    # gains and the router stay float32 whatever the blocks compute in
    p = jax.tree_util.tree_map_with_path(
        lambda path, a: a if a.ndim == 1 or path[-1].key == "wg"
        else a.astype(cdt), p)
    B, T, _ = x.shape
    d, H, Hkv = cfg.head_dim, cfg.num_attention_heads, \
        cfg.num_key_value_heads
    eps = cfg.rms_norm_eps
    window = cfg.sliding_window if kind == SLIDING else None
    a = rmsnorm(x, p["input_layernorm"], eps)
    at = p["attn"]
    with jax.named_scope("attn.band" if window else "attn.full"):
        q = rmsnorm((a @ at["wq"]).reshape(B, T, H, d), at["q_norm"], eps)
        k = rmsnorm((a @ at["wk"]).reshape(B, T, Hkv, d), at["k_norm"], eps)
        v = (a @ at["wv"]).reshape(B, T, Hkv, d)
        inv_freq, factor = rope_table(cfg, kind)
        q = rope(q, positions, inv_freq, factor)
        k = rope(k, positions, inv_freq, factor)
        o = attend(q, k, v, 1.0 / math.sqrt(d), window, cfg.attn_impl)
        x = x + (o @ at["wo"]).astype(x.dtype)
    m = rmsnorm(x, p["post_attention_layernorm"], eps)
    with jax.named_scope("moe.experts"):
        f, sel = routed_ffn(p["ffn"], m, cfg)
    return x + f.astype(x.dtype), sel


def loss_and_chosen(params, ids, cfg: MellumConfig):
    """ids [B, T] -> (mean next-token cross-entropy over the vocabulary
    held, float32; the experts every layer chose, int32 [L, B T, k])."""
    B, T = ids.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = jnp.take(params["embed"], ids, axis=0)
    if cfg.amp_dtype:
        x = x.astype(jnp.dtype(cfg.amp_dtype))
    sels = []
    for l, kind in enumerate(cfg.layer_types):
        fn = partial(layer, cfg=cfg, kind=kind)
        if cfg.remat:
            fn = jax.checkpoint(fn)
        p = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        x, sel = fn(p, x, positions)
        sels.append(sel)
    with jax.named_scope("head.loss"):
        x = rmsnorm(x, params["norm"], cfg.rms_norm_eps)
        logits = jnp.einsum("btd,dv->btv", x,
                            params["head"].astype(x.dtype),
                            preferred_element_type=jnp.float32)[:, :-1]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ids[:, 1:, None], -1)[..., 0]
        loss = jnp.mean(logz - gold)
    return loss, jnp.stack(sels)


class MellumTrainModel:
    """What `HybridParallelTrainStep` asks of a model (docs/TRAINING.md):
    parameters made on the device, their PartitionSpecs, which leaves
    decay, and `loss(params, ids, key)`. The loss hands back the experts
    it chose and the trainer keeps their tally (`num_experts`,
    `experts_held`)."""

    decay = DECAYED
    has_aux = True
    parallel_axes = ()      # no axis above 1: see `refuse`

    def __init__(self, cfg: MellumConfig):
        self.cfg = cfg
        self.num_experts = cfg.num_experts
        self.experts_held = cfg.held
        self.tally_layers = cfg.num_hidden_layers

    def refuse(self, axis: str, size: int):
        missing = {
            "dp": "the flash kernel on each replica's rows (per_shard, as "
                  "models/gpt.py wraps it)",
            "pp": "a stage function over this core's layer kinds (and the "
                  "head on the last stage only)",
            "tp": "PartitionSpecs over heads and the experts' width, and "
                  "the flash kernel's band per shard",
            "sp": "a ring over the sequence that knows the band",
            "ep": "the exchange of pairs between ranks (all_to_all before "
                  "and after the grouped products): experts_held states "
                  "one rank's share and nothing stands in for the others",
        }[axis]
        raise NotImplementedError(
            f"models/mellum.py trains on one device: {axis}={size} needs "
            f"{missing}")

    def init_params(self, seed: int, shardings=None):
        return init_params(self.cfg, seed, shardings)

    def param_specs(self):
        return param_specs(self.cfg)

    def loss(self, params, ids, key=None):
        """(loss, {"chosen": int32 [L, B T, k]}); no dropout: `key` is
        unused."""
        loss, chosen = loss_and_chosen(params, ids, self.cfg)
        return loss, {"chosen": chosen}

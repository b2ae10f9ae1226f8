"""GPT-style causal decoder — BASELINE config 5 flagship (GPT-3 1.3B).

Reference parity target: the PaddleNLP GPT built on the reference
transformer stack (python/paddle/nn/layer/transformer.py) and trained with
PipelineOptimizer (/root/reference/python/paddle/fluid/optimizer.py:3666).
Here the model has a **functional core**: params are a pytree, the forward
is a pure jax function, and one implementation serves every execution mode —

  * single device / dygraph (`GPTForCausalLM` Layer wraps the core),
  * dp x tp via GSPMD PartitionSpec rules (`gpt_sharding_rules`),
  * pipeline parallel via stacked per-stage params
    (paddle_tpu.parallel.pipeline + hybrid.HybridParallelTrainStep).

Blocks are pre-LN transformer decoders; block params are stacked [L, ...]
and scanned with lax.scan (compile time stays O(1) in depth — the
TPU answer to the reference's per-op graph growing with depth).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["GPTConfig", "init_gpt_params", "gpt_param_specs", "gpt_forward",
           "gpt_loss", "gpt_block_fn", "decoder_tail", "GPTForCausalLM"]


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_position_embeddings: int = 1024
    intermediate_size: int | None = None  # default 4*hidden
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    dropout: float = 0.0
    amp_dtype: str | None = None  # "bfloat16" casts block compute
    attn_impl: str = "xla"  # "xla" | "flash" (Pallas) | "ring" (sp mesh)
    # Mixture-of-Experts (num_experts > 0 replaces every block's dense FFN
    # with a routed expert bank — parallel/moe.py, "ep" mesh axis)
    num_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # rematerialise each block in backward: the lax.scan over layers would
    # otherwise stash every layer's attention probs ([L,B,H,T,T] — OOM at
    # 350M/seq-1024 on one v5e chip)
    remat: bool = True
    # epilogue-fused decoder sub-blocks (ops/pallas_block.py): the
    # attention-out projection + residual + LN2 and the FFN + residual
    # run as GEMM-epilogue Pallas programs where the autobench gate
    # measures them faster than the composed XLA chain (dense blocks,
    # dropout=0 path only; False pins the composed chain everywhere)
    fused_blocks: bool = True

    def __post_init__(self):
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.hidden_size
        assert self.hidden_size % self.num_heads == 0

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_layers", 4)
        kw.setdefault("num_heads", 4)
        kw.setdefault("max_position_embeddings", 128)
        return cls(**kw)

    @classmethod
    def gpt2_small(cls, **kw):
        return cls(**kw)

    @classmethod
    def gpt3_1p3b(cls, **kw):
        """GPT-3 XL: 24 layers, d_model 2048, 16 heads of 128."""
        kw.setdefault("hidden_size", 2048)
        kw.setdefault("num_layers", 24)
        kw.setdefault("num_heads", 16)
        kw.setdefault("max_position_embeddings", 2048)
        return cls(**kw)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_gpt_params(cfg: GPTConfig, seed: int = 0) -> dict:
    """Pytree: embeddings + stacked blocks [L, ...] + final LN. LM head is
    tied to wte (Megatron/GPT-2 convention)."""
    rng = np.random.RandomState(seed)
    D, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    s = cfg.initializer_range

    def norm(*shape):
        return rng.normal(0.0, s, shape).astype(np.float32)

    blocks = {
        "ln1_s": np.ones((L, D), np.float32),
        "ln1_b": np.zeros((L, D), np.float32),
        "wq": norm(L, D, D), "bq": np.zeros((L, D), np.float32),
        "wk": norm(L, D, D), "bk": np.zeros((L, D), np.float32),
        "wv": norm(L, D, D), "bv": np.zeros((L, D), np.float32),
        # output/down projections scaled 1/sqrt(2L) (GPT-2 residual scaling)
        "wo": norm(L, D, D) / math.sqrt(2 * L),
        "bo": np.zeros((L, D), np.float32),
        "ln2_s": np.ones((L, D), np.float32),
        "ln2_b": np.zeros((L, D), np.float32),
    }
    E = cfg.num_experts
    if E > 0:
        blocks.update({
            "wg": norm(L, D, E),
            "we_up": norm(L, E, D, F),
            "be_up": np.zeros((L, E, F), np.float32),
            "we_down": norm(L, E, F, D) / math.sqrt(2 * L),
            "be_down": np.zeros((L, E, D), np.float32),
        })
    else:
        blocks.update({
            "w_up": norm(L, D, F), "b_up": np.zeros((L, F), np.float32),
            "w_down": norm(L, F, D) / math.sqrt(2 * L),
            "b_down": np.zeros((L, D), np.float32),
        })
    return {
        "wte": norm(cfg.vocab_size, D),
        "wpe": norm(cfg.max_position_embeddings, D),
        "blocks": blocks,
        "lnf_s": np.ones((D,), np.float32),
        "lnf_b": np.zeros((D,), np.float32),
    }


def gpt_param_specs(pp_stacked: bool = False, moe: bool = False) -> dict:
    """PartitionSpec pytree (megatron-style tp; blocks get a leading "pp"
    dim when stacked per-stage; expert banks shard E over "ep"). Axes not
    present in the mesh are dropped by ShardingRules._restrict-like
    resolution in hybrid.py."""

    def blk(*entries):
        return P(*(("pp",) if pp_stacked else ()), None, *entries)

    blocks = {
        "ln1_s": blk(None), "ln1_b": blk(None),
        "wq": blk(None, "tp"), "bq": blk("tp"),
        "wk": blk(None, "tp"), "bk": blk("tp"),
        "wv": blk(None, "tp"), "bv": blk("tp"),
        "wo": blk("tp", None), "bo": blk(None),
        "ln2_s": blk(None), "ln2_b": blk(None),
    }
    if moe:
        blocks.update({
            "wg": blk(None, None),
            "we_up": blk("ep", None, "tp"), "be_up": blk("ep", "tp"),
            "we_down": blk("ep", "tp", None), "be_down": blk("ep", None),
        })
    else:
        blocks.update({
            "w_up": blk(None, "tp"), "b_up": blk("tp"),
            "w_down": blk("tp", None), "b_down": blk(None),
        })
    return {
        "wte": P("tp", None),
        "wpe": P(),
        "blocks": blocks,
        "lnf_s": P(),
        "lnf_b": P(),
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _ln(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _causal_attention(q, k, v, n_heads, impl="xla"):
    """q,k,v: [B, T, D] -> [B, T, D]; softmax in fp32."""
    B, T, D = q.shape
    hd = D // n_heads
    q = q.reshape(B, T, n_heads, hd)
    k = k.reshape(B, T, n_heads, hd)
    v = v.reshape(B, T, n_heads, hd)
    if impl == "ring":
        # sequence-parallel ring attention over the ambient sp mesh axis
        # (parallel/sequence_parallel.py); T here is the LOCAL shard
        from ..parallel.sequence_parallel import current_ring, \
            ring_attention
        ctx = current_ring()
        if ctx is None:
            raise RuntimeError(
                "attn_impl='ring' needs an enclosing ring_context(mesh, "
                "axis)")
        mesh, axis = ctx
        o = ring_attention(q.transpose(0, 2, 1, 3),
                           k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3), mesh, axis,
                           causal=True)
        return o.transpose(0, 2, 1, 3).reshape(B, T, D)
    if impl == "flash":
        from ..ops.flash_attention import _flash_wins
        from ..ops.pallas_attention import flash_attention
        from ..parallel.sharding import (_restrict, current_kernel_mesh,
                                         per_shard)
        qh, kh, vh = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        attend = partial(flash_attention, causal=True)
        local = qh
        mesh = current_kernel_mesh()
        if mesh is not None:
            # the kernel on each device's shard: batch over dp, heads
            # over tp, the layout gpt_param_specs already gives q/k/v
            spec = _restrict(P("dp", "tp", None, None), mesh)
            attend = per_shard(attend, mesh, spec, 3)
            local = jax.ShapeDtypeStruct(
                NamedSharding(mesh, spec).shard_shape(qh.shape), qh.dtype)
        # same measure-once gate as the fused_attention op: the Pallas
        # kernel only keeps the hot path on shapes where it beats XLA
        if _flash_wins(local, local, local, None, None, True):
            o = attend(qh, kh, vh)
            return o.transpose(0, 2, 1, 3).reshape(B, T, D)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return o.reshape(B, T, D)


def _dropout(x, rate, key):
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


def decoder_tail(p, a2, x2, cfg: GPTConfig):
    """Post-attention tail of a dense pre-LN decoder block, 2-d form:

        z = x2 + a2 @ wo + bo
        h = LN2(z)
        out = z + gelu_tanh(h @ w_up + b_up) @ w_down + b_down

    a2/x2: (M, D). ONE source of truth for this math: gpt_block_fn AND
    the serving decode model (prefill/decode bodies) both call it, so
    the serving token-parity contract survives the fused paths. Each
    sub-block runs as an epilogue-fused Pallas program
    (ops/pallas_block.py) where the autobench gate measures it faster
    than the composed XLA chain at this (M, D) shape; everywhere else
    the composed chain below runs bit-identically to the pre-PR-7
    code."""
    cdt = x2.dtype
    wo, bo = p["wo"].astype(cdt), p["bo"].astype(cdt)
    w_up, b_up = p["w_up"].astype(cdt), p["b_up"].astype(cdt)
    w_down, b_down = p["w_down"].astype(cdt), p["b_down"].astype(cdt)
    eps = cfg.layer_norm_eps
    m, d = x2.shape
    f = w_up.shape[-1]
    it = cdt.itemsize
    seed = jnp.zeros((1,), jnp.int32)
    z = h = None
    if cfg.fused_blocks:
        from ..ops.pallas_block import (can_use_fused_ffn_ln,
                                        can_use_fused_out_ln,
                                        ffn_ln_wins, fused_ffn_ln,
                                        fused_out_ln, out_ln_wins)
        if can_use_fused_out_ln(m, d, d, it) \
                and out_ln_wins(m, d, d, cdt, 0.0, eps):
            z, h = fused_out_ln(a2, wo, bo, x2, p["ln2_s"], p["ln2_b"],
                                seed, 0.0, eps)
    if z is None:
        z = x2 + (a2 @ wo + bo).astype(x2.dtype)
        h = _ln(z, p["ln2_s"], p["ln2_b"], eps)
    if cfg.fused_blocks and can_use_fused_ffn_ln(m, d, f, it) \
            and ffn_ln_wins(m, d, f, cdt, "gelu_tanh", "none"):
        ones = jnp.ones((d,), jnp.float32)
        zeros = jnp.zeros((d,), jnp.float32)
        return fused_ffn_ln(h.astype(cdt), w_up, b_up, w_down, b_down,
                            z, ones, zeros, seed, "gelu_tanh", "none",
                            0.0, eps)
    u = jax.nn.gelu(h.astype(cdt) @ w_up + b_up, approximate=True)
    dn = u @ w_down + b_down
    return z + dn.astype(z.dtype)


def gpt_block_fn(p: dict, x, cfg: GPTConfig, key=None):
    """One pre-LN decoder block; p leaves are unstacked ([D,...]).

    `key` enables residual dropout (GPT-2 placement: after the attention
    out-projection and after the FFN down-projection); None or
    cfg.dropout=0 is the deterministic path. The pipeline engines re-derive
    the same key at recompute time, so rematerialised backward sees
    identical masks.

    Returns (x, aux): aux is the MoE load-balance loss of this block's
    routed FFN (0.0 for the dense FFN)."""
    cdt = jnp.dtype(cfg.amp_dtype) if cfg.amp_dtype else x.dtype
    c = lambda a: a.astype(cdt)
    drop = cfg.dropout if (cfg.dropout and key is not None) else 0.0
    if drop:
        k1, k2 = jax.random.split(key)
    h = _ln(x, p["ln1_s"], p["ln1_b"], cfg.layer_norm_eps)
    q = c(h) @ c(p["wq"]) + c(p["bq"])
    k = c(h) @ c(p["wk"]) + c(p["bk"])
    v = c(h) @ c(p["wv"]) + c(p["bv"])
    a = _causal_attention(q, k, v, cfg.num_heads, cfg.attn_impl)
    if cfg.num_experts == 0 and not drop:
        # dense deterministic path: attention-out + FFN sub-blocks as
        # epilogue-fused Pallas programs behind the autobench gate
        # (composed-chain fallback inside decoder_tail is bit-identical
        # to the previous inline code)
        B, T, D = x.shape
        x = decoder_tail(p, c(a).reshape(B * T, D),
                         x.reshape(B * T, D), cfg).reshape(B, T, D)
        return x, jnp.zeros((), jnp.float32)
    proj = a @ c(p["wo"]) + c(p["bo"])
    if drop:
        proj = _dropout(proj, drop, k1)
    x = x + proj.astype(x.dtype)
    h = _ln(x, p["ln2_s"], p["ln2_b"], cfg.layer_norm_eps)
    if cfg.num_experts > 0:
        from ..parallel.moe import moe_ffn
        y, aux = moe_ffn(
            c(h), p["wg"], p["we_up"], p["be_up"], p["we_down"],
            p["be_down"], capacity_factor=cfg.moe_capacity_factor,
            top_k=cfg.moe_top_k)
        if drop:
            y = _dropout(y, drop, k2)
        return x + y.astype(x.dtype), aux
    u = jax.nn.gelu(c(h) @ c(p["w_up"]) + c(p["b_up"]), approximate=True)
    d = u @ c(p["w_down"]) + c(p["b_down"])
    if drop:
        d = _dropout(d, drop, k2)
    x = x + d.astype(x.dtype)
    return x, jnp.zeros((), jnp.float32)


def _embed(params, ids, cfg: GPTConfig):
    T = ids.shape[-1]
    if T > params["wpe"].shape[0]:
        raise ValueError(
            f"sequence length {T} exceeds max_position_embeddings="
            f"{params['wpe'].shape[0]}")
    x = jnp.take(params["wte"], ids, axis=0) + params["wpe"][:T]
    if cfg.amp_dtype:
        x = x.astype(jnp.dtype(cfg.amp_dtype))
    return x


def _head(params, x, cfg: GPTConfig):
    x = _ln(x, params["lnf_s"], params["lnf_b"], cfg.layer_norm_eps)
    # logits in fp32 for a stable softmax-xent
    return x.astype(jnp.float32) @ params["wte"].T.astype(jnp.float32)


def block_body(cfg: GPTConfig):
    """Scan body over stacked block params, rematerialised per layer when
    cfg.remat (jax.checkpoint — reference RecomputeOptimizer semantics at
    layer granularity). ys is the per-layer MoE aux loss."""
    def body(h, blk):
        return gpt_block_fn(blk, h, cfg)

    if cfg.remat:
        ck = jax.checkpoint(lambda blk, h: gpt_block_fn(blk, h, cfg))
        return lambda h, blk: ck(blk, h)
    return body


def block_body_keyed(cfg: GPTConfig):
    """Like block_body but the scan xs is (blk, per-layer dropout key)."""
    def inner(blk, h, key):
        return gpt_block_fn(blk, h, cfg, key)

    if cfg.remat:
        inner = jax.checkpoint(inner)

    def body(h, xs):
        blk, key = xs
        return inner(blk, h, key)

    return body


def gpt_forward_aux(params: dict, ids, cfg: GPTConfig, key=None):
    """(logits [B, T, V], aux): aux = summed MoE load-balance loss over
    layers (0.0 for dense models). `key` turns on dropout (training)."""
    x = _embed(params, ids, cfg)
    if cfg.dropout and key is not None:
        kemb, key = jax.random.split(key)
        x = _dropout(x, cfg.dropout, kemb)
        lkeys = jax.random.split(key, cfg.num_layers)
        x, auxs = jax.lax.scan(block_body_keyed(cfg), x,
                               (params["blocks"], lkeys))
    else:
        x, auxs = jax.lax.scan(block_body(cfg), x, params["blocks"])
    return _head(params, x, cfg), jnp.sum(auxs)


def gpt_forward(params: dict, ids, cfg: GPTConfig, key=None):
    """ids [B, T] int -> logits [B, T, V]. Blocks run under lax.scan over
    the stacked [L, ...] leaves."""
    return gpt_forward_aux(params, ids, cfg, key=key)[0]


def gpt_loss(params: dict, ids, cfg: GPTConfig, logits=None, key=None):
    """Mean next-token cross entropy; predicts ids[:,1:] from ids[:,:-1].
    MoE models add cfg.moe_aux_weight * load-balance aux."""
    aux = None
    if logits is None:
        logits, aux = gpt_forward_aux(params, ids, cfg, key=key)
    logits = logits[:, :-1]
    labels = ids[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    loss = jnp.mean(logz - gold)
    if aux is not None and cfg.num_experts > 0:
        loss = loss + cfg.moe_aux_weight * aux
    return loss


# ---------------------------------------------------------------------------
# dygraph wrapper (API parity with the Layer zoo)
# ---------------------------------------------------------------------------

from ..fluid.dygraph.layers import Layer as _Layer
from ..fluid.dygraph.varbase import Tensor as _Tensor


class GPTForCausalLM(_Layer):
    """Layer wrapper binding framework Parameters onto the functional core
    (trainable with the jit.functional.TrainStep pattern)."""

    def __init__(self, cfg: GPTConfig, seed: int = 0):
        super().__init__()
        self.cfg = cfg
        flat, self._treedef = jax.tree_util.tree_flatten(
            init_gpt_params(cfg, seed))
        self._param_list = []
        for i, leaf in enumerate(flat):
            p = _Tensor(jnp.asarray(leaf), stop_gradient=False,
                        persistable=True)
            self.add_parameter(f"p_{i}", p)
            self._param_list.append(p)

    def param_tree(self):
        return jax.tree_util.tree_unflatten(
            self._treedef, [p._value for p in self._param_list])

    def forward(self, ids):
        ids_v = ids._value if isinstance(ids, _Tensor) else ids
        return _Tensor(gpt_forward(self.param_tree(), ids_v, self.cfg),
                       stop_gradient=False)

    def loss(self, ids):
        ids_v = ids._value if isinstance(ids, _Tensor) else ids
        return _Tensor(gpt_loss(self.param_tree(), ids_v, self.cfg),
                       stop_gradient=False)

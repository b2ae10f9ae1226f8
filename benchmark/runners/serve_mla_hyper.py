"""Runner of a served latent-attention configuration whose residual is
several streams mixed by hyper-connections (`model_type: xing4_0`: the
DeepSeek-V3 block with a low-rank query under YaRN, on `hc_mult` streams).
`runners/serve_mla.py`'s run, engine and comparison (the model is the same
`LatentDecodeModel`, the cache the same latent row, the routing replayed)
with this file's table of published keys: it takes `q_lora_rank`, the
`rope_scaling` group and the `hc_*` keys that `serve_mla.FIXED` refuses.
"""
from __future__ import annotations

import types

# first of all: a program without the streams' steps fails here, at once
from paddle_tpu.models.layers import hc_coefficients  # noqa: F401

from . import serve, serve_mla
from .serve_hybrid import _compare, _sample

PUBLISHED = serve_mla.PUBLISHED + (
    "q_lora_rank", "rope_scaling", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
    "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")
# published keys that say what this block does NOT have, or has in one
# form only: the program builds nothing for another value
FIXED = {"hidden_act": "silu", "tie_word_embeddings": False,
         "attention_bias": False, "scoring_func": "sigmoid",
         "topk_method": "noaux_tc", "moe_layer_freq": 1, "ep_size": 1,
         # the multi-token-prediction module is a training objective and
         # an optional draft head: not loaded (`reduced`, 1 -> 0)
         "num_nextn_predict_layers": 0}
MODEL_KEYS = tuple(k for k in PUBLISHED
                   if k not in ("num_key_value_heads", "head_dim",
                                "qk_head_dim")) + ("initializer_range",)


def sizes_of(config: dict) -> dict:
    """As `serve_mla.sizes_of`, over this file's keys. `head_dim` and
    `qk_head_dim` are not in the published config: the reference and the
    readers take the rope part and nope + rope."""
    for key, want in FIXED.items():
        if config.get(key) != want:
            raise ValueError(f"{key} = {config.get(key)!r}: only {want!r} "
                             f"is built")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention: as many KV heads as heads")
    config = {"head_dim": config["qk_rope_head_dim"],
              "qk_head_dim": config["qk_nope_head_dim"]
              + config["qk_rope_head_dim"], **config}
    sizes = {k: config[k] for k in PUBLISHED}
    sizes.update(config.get("sizes_assumed", {}))
    sizes.update(
        num_experts=sizes["n_routed_experts"],
        num_dense_layers=sizes["first_k_dense_replace"],
        conv_L_cache=1,
        layer_types=["full_attention"] * sizes["num_hidden_layers"])
    return sizes


def model_config(config: dict):
    """The program's DeepseekV3Config at the file's sizes and dtype."""
    from paddle_tpu.models.deepseek_v3 import DeepseekV3Config
    s = sizes_of(config)
    return DeepseekV3Config(dtype=config["dtype"],
                            **{k: s[k] for k in MODEL_KEYS})


def _rebound(fn, **names):
    return types.FunctionType(fn.__code__, {**fn.__globals__, **names},
                              fn.__name__)


_engine = _rebound(serve_mla._engine, model_config=model_config)
_run = _rebound(serve.run, _engine=_engine, _sample=_sample,
                _compare=_compare)


def run(ctx) -> dict:
    ctx.config["sizes"] = sizes_of(ctx.config)
    out = _run(ctx)
    out["stats_log"] = ctx.stats_log
    out["paged_bytes_per_token"] = ctx.paged_bytes_per_token
    return out

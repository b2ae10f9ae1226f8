"""Runner of a served configuration whose attention caches a latent row a
token in place of keys and values (`paddle_tpu.serving.LatentDecodeModel`:
multi-head latent attention, expanded in prefill and absorbed in decode,
routed experts beside shared ones). Everything but the engine's builder is
`runners/serve.py`'s run with `runners/serve_hybrid.py`'s comparison: the
experts are routed, so `correct` replays the program's routing in the
float32 reference (that module says why), the sample is drawn from the
greedy requests that handed their routing back, and the experts' tallies
are logged round the traced span. Bound as `serve_hybrid.py` binds its own
(PERF.md, Open questions: let `serve.run` take them as arguments in the
next `benchmark` PR).
"""
from __future__ import annotations

import time
import types

from . import serve
from .serve_hybrid import _compare, _flag_routing, _log_stats, _sample

PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_hidden_layers", "first_k_dense_replace",
    "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "qk_nope_head_dim", "qk_rope_head_dim", "qk_head_dim", "v_head_dim",
    "kv_lora_rank", "n_group", "topk_group", "norm_topk_prob",
    "routed_scaling_factor", "rope_theta", "rms_norm_eps",
    "max_position_embeddings")
# published keys that say what this block does NOT have, or has in one
# form only: the program builds nothing for another value
FIXED = {"hidden_act": "silu", "tie_word_embeddings": False,
         "rope_scaling": None, "attention_bias": False, "q_lora_rank": None,
         "scoring_func": "sigmoid", "topk_method": "noaux_tc",
         "rope_interleave": True, "moe_layer_freq": 1}
# what the program's config takes of the sizes (the rest describe the
# same numbers twice: `qk_head_dim` = nope + rope, `num_key_value_heads` =
# the heads, `head_dim` = the rope part)
MODEL_KEYS = tuple(k for k in PUBLISHED
                   if k not in ("num_key_value_heads", "head_dim",
                                "qk_head_dim")) + ("initializer_range",)


def sizes_of(config: dict) -> dict:
    """The sizes the program and the reference are built from: the
    published keys, which the configuration's file holds at its top level
    under the names `config.json` gives them, and the sizes assumed. Four
    more under the names `readers/hybrid.py::_fields` indexes, so that the
    expert layer's and the sampler's readers fill their patterns from this
    cell's own numbers."""
    for key, want in FIXED.items():
        if config.get(key) != want:
            raise ValueError(f"{key} = {config.get(key)!r}: only {want!r} "
                             f"is built")
    if config["qk_head_dim"] != config["qk_nope_head_dim"] \
            + config["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is not nope + rope")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention: as many KV heads as heads")
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


def _engine(ctx):
    """The program under test, built as a user builds it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving import Engine, LatentDecodeModel

    sizes = ctx.config["sizes"]
    ecfg, dtype = ctx.config["engine"], ctx.config["dtype"]
    t0 = time.perf_counter()
    params = ctx.reference().make_weights(sizes, ctx.seed, jnp.dtype(dtype))
    jax.block_until_ready(params)
    ctx.say(f"weights: seed {ctx.seed}, {dtype}, on the device in "
            f"{time.perf_counter() - t0:.2f}s")
    model = LatentDecodeModel(model_config(ctx.config), params=params)
    eng = Engine(model, num_slots=ecfg["num_slots"],
                 num_pages=ecfg["num_pages"], page_size=ecfg["page_size"],
                 max_seq_len=ecfg["max_seq_len"],
                 max_queue=ecfg.get("max_queue", 256))
    _flag_routing(eng)
    _log_stats(ctx, eng)
    # the engine's own gauge, as a scrape of its metrics reads it
    from paddle_tpu.observability import registry
    ctx.paged_bytes_per_token = registry.REGISTRY.get(
        "paddle_tpu_serving_paged_bytes_per_token").labels(
            engine=eng.engine_id).value
    return eng, params


_run = types.FunctionType(
    serve.run.__code__,
    {**serve.run.__globals__, "_engine": _engine, "_sample": _sample,
     "_compare": _compare}, "run")


def run(ctx) -> dict:
    # first of all: a program without this model fails here, at once
    from paddle_tpu.serving import LatentDecodeModel  # noqa: F401
    ctx.config["sizes"] = sizes_of(ctx.config)
    out = _run(ctx)
    out["stats_log"] = ctx.stats_log
    out["paged_bytes_per_token"] = ctx.paged_bytes_per_token
    return out

"""Runner of a served configuration whose layers keep a recurrence's state
per slot beside a few attention layers (`paddle_tpu.serving.
RecurrentDecodeModel`: the state and the convolution's taps in slot parts,
the attention layers' shared K | V row in the pages). Everything but the
engine's builder is `runners/serve.py`'s: its `run` executes here with
`_engine` bound to this module's, as `runners/serve_looped.py` binds its
own (PERF.md, Open questions: let `serve.run` take the builder as an
argument in the next `benchmark` PR). `correct` is GPT's comparison
(`serve._sample`; a dense decoder has no discontinuous choice to replay),
read from what the timed path produced: a prompt prefilled by the chunked
scan into a slot some other request had held, then every token decoded
through that slot's state and the pages. It holds the run to TWO limits
(`_compare`): the WIDEST gap of a served greedy token's logit below the
float32 reference's best, which a wrong program fails, and the MEAN of
those gaps over all the served tokens. A served token differs from the
reference's first choice where the two lie closer than the program's
noise, by about that noise, so the mean grows with the noise's square and
is steady over some two thousand positions: it is what tells a state
rounded to bfloat16 at every step from the configuration's own precision,
which the widest gap, one position's luck, cannot.
"""
from __future__ import annotations

import time
import types

import numpy as np

from . import serve
from ..lib import recurrent_counts

PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "attn_layer_period",
    "attn_layer_offset", "expert_layer_period", "expert_layer_offset",
    "num_experts", "num_experts_per_tok", "mamba_d_state", "mamba_d_conv",
    "mamba_expand", "mamba_dt_rank", "mamba_conv_bias", "mamba_proj_bias",
    "rms_norm_eps", "max_position_embeddings", "tie_word_embeddings")
# published keys that say what this block does NOT have, or has in one
# form only: the program builds nothing for another value
FIXED = {"hidden_act": "silu", "sliding_window": None, "num_experts": 1}


def sizes_of(config: dict) -> dict:
    """The sizes the program and the reference are built from: the
    published keys, which the configuration's file holds at its top level
    under the names `config.json` gives them, and the sizes assumed. With
    them the names `readers/hybrid.py::_fields` indexes, so that the
    sampler's reader runs here: `layer_types` as the offsets give them, no
    dense-layer prefix to tell from expert layers, the convolution's
    length."""
    for key, want in FIXED.items():
        if config.get(key) != want:
            raise ValueError(f"{key} = {config.get(key)!r}: only {want!r} "
                             f"is built")
    sizes = {k: config[k] for k in PUBLISHED}
    sizes.update(config.get("sizes_assumed", {}))
    if sizes["head_dim"] * sizes["num_attention_heads"] \
            != sizes["hidden_size"]:
        raise ValueError("head_dim: hidden_size / num_attention_heads")
    sizes["layer_types"] = [
        "attention" if l % sizes["attn_layer_period"]
        == sizes["attn_layer_offset"] else "mamba"
        for l in range(sizes["num_hidden_layers"])]
    sizes["num_dense_layers"] = sizes["num_hidden_layers"]
    sizes["conv_L_cache"] = sizes["mamba_d_conv"]
    return sizes


def model_config(config: dict):
    """The program's JambaConfig at the file's sizes and dtype."""
    from paddle_tpu.models.jamba import JambaConfig
    s = sizes_of(config)
    return JambaConfig(dtype=config["dtype"],
                       initializer_range=s["initializer_range"],
                       **{k: s[k] for k in PUBLISHED})


def _engine(ctx):
    """The program under test, built as a user builds it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.observability import registry
    from paddle_tpu.serving import Engine, RecurrentDecodeModel

    sizes = ctx.config["sizes"]
    ecfg, dtype = ctx.config["engine"], ctx.config["dtype"]
    t0 = time.perf_counter()
    params = ctx.reference().make_weights(sizes, ctx.seed, jnp.dtype(dtype))
    jax.block_until_ready(params)
    ctx.say(f"weights: seed {ctx.seed}, {dtype}, on the device in "
            f"{time.perf_counter() - t0:.2f}s")
    model = RecurrentDecodeModel(model_config(ctx.config), params=params)
    eng = Engine(model, num_slots=ecfg["num_slots"],
                 num_pages=ecfg["num_pages"], page_size=ecfg["page_size"],
                 max_seq_len=ecfg["max_seq_len"],
                 max_queue=ecfg.get("max_queue", 256))
    # the engine's own gauge, as a scrape of its metrics reads it
    ctx.slot_state_bytes = registry.REGISTRY.get(
        "paddle_tpu_serving_slot_state_bytes").labels(
            engine=eng.engine_id).value
    # the state's precision is part of the configuration (`sizes_assumed`:
    # ssm_state_dtype): a cache that keeps it in another reads other bytes
    item = recurrent_counts.ITEMSIZE
    want = recurrent_counts.state_bytes_a_slot(
        sizes, item[sizes["ssm_state_dtype"]], item[dtype])
    a_slot = ctx.slot_state_bytes / ecfg["num_slots"]
    ctx.check("bytes of recurrent state a slot, the engine's gauge against "
              "the configuration's sizes and dtypes", a_slot, want,
              ok=a_slot == want)
    return eng, params


def _compare(ctx, params, sample):
    """`serve._compare` with a second reading: for each sampled request the
    reference runs once over its prompt and its served tokens; the widest
    and the mean gap of a served token below the reference's best logit
    are each held to a limit. With `ctx.control`, the same two for the
    tokens that precision puts first."""
    if not sample:
        return
    ref, sizes = ctx.reference(), ctx.config["sizes"]
    limits = ctx.config["correct"]
    t0 = time.perf_counter()
    served, ctrl = [], []
    for t in sample:
        p, g = t.item["prompt_len"], len(t.req.generated)
        # the reference's recurrence is a serial scan over the positions it
        # is given: a power of two that holds the request (padding behind
        # the real tokens reaches no earlier position), not the longest
        T = min(int(limits["reference_length"]),
                max(512, 1 << (p + g - 1).bit_length()))
        ids = np.zeros((T,), np.int32)
        ids[:p] = t.item["prompt"]
        ids[p:p + g] = t.req.generated
        alt = None
        if ctx.control:
            _, _, best = ref.next_token_gaps(params, ids, sizes, ctx.control)
            alt = np.concatenate([ids[:1], np.asarray(best)])
        gap, gap_alt, _ = ref.next_token_gaps(params, ids, sizes, "f32", alt)
        # position p-1 predicts the first served token, p+g-2 the last
        served.append(np.asarray(gap)[p - 1:p + g - 1])
        ctrl.append(np.asarray(gap_alt)[p - 1:p + g - 1])
    served, ctrl = np.concatenate(served), np.concatenate(ctrl)
    ctx.say(f"reference: {len(sample)} requests, {served.size} served "
            f"tokens, {int(np.sum(served > 0))} of them not the reference's "
            f"first, {time.perf_counter() - t0:.1f}s")
    if ctx.control:
        ctx.control_readings["gap"] = float(ctrl.max())
        ctx.control_readings["mean_gap"] = float(ctrl.mean())
        ctx.say(f"CONTROL {ctx.control}: of the tokens it puts first, the "
                f"widest gap below the reference's best logit "
                f"{float(ctrl.max())!r} and the mean {float(ctrl.mean())!r} "
                f"(program: {float(served.max())!r}, "
                f"{float(served.mean())!r})")
    ctx.check("widest gap of a served greedy token below the reference's "
              "best logit", float(served.max()), float(limits["gap_limit"]))
    ctx.check("mean gap of the served greedy tokens below the reference's "
              "best logit", float(served.mean()),
              float(limits["mean_gap_limit"]))


_run = types.FunctionType(
    serve.run.__code__,
    {**serve.run.__globals__, "_engine": _engine, "_compare": _compare},
    "run")


def run(ctx) -> dict:
    # first of all: a program without this model fails here, at once
    from paddle_tpu.serving import RecurrentDecodeModel  # noqa: F401
    ctx.config["sizes"] = sizes_of(ctx.config)
    out = _run(ctx)
    out["slot_state_bytes"] = ctx.slot_state_bytes
    return out

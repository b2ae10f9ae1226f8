"""Runner of a served configuration whose layers run several times a token
(`paddle_tpu.serving.LoopedDecodeModel`: K/V of every layer of every pass
in the pages, tallies by pass). Everything but the engine's builder is
`runners/serve.py`'s: its `run` executes here with `_engine` bound to this
module's, as `runners/serve_hybrid.py` binds its own (PERF.md, Open
questions: let `serve.run` take the builder as an argument in the next
`benchmark` PR). `correct` is GPT's comparison as it stands
(`serve._sample`, `serve._compare`): a dense looped decoder has no
discontinuous choice to replay, so the widest gap of a served greedy
token's logit below the float32 reference's best is precision alone.
"""
from __future__ import annotations

import time
import types

from . import serve

PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
    "rms_norm_eps", "max_position_embeddings", "total_ut_steps",
    "early_exit_threshold")
# published keys that say what this architecture does NOT have: the
# program builds nothing for another value
FIXED = {"hidden_act": "silu", "tie_word_embeddings": False,
         "use_sliding_window": False, "rope_scaling": None,
         "sliding_window": None}


def sizes_of(config: dict) -> dict:
    """The sizes the program and the reference are built from: the
    published keys, which the configuration's file holds at its top level
    under the names `config.json` gives them, and the sizes assumed."""
    for key, want in FIXED.items():
        if config.get(key) != want:
            raise ValueError(f"{key} = {config.get(key)!r}: only {want!r} "
                             f"is built")
    if len(config["layer_types"]) != config["num_hidden_layers"] \
            or set(config["layer_types"]) != {"full_attention"}:
        raise ValueError("layer_types: every layer is full attention")
    sizes = {k: config[k] for k in PUBLISHED}
    sizes.update(config.get("sizes_assumed", {}))
    return sizes


def model_config(config: dict):
    """The program's OuroConfig at the file's sizes and dtype."""
    from paddle_tpu.models.ouro import OuroConfig
    return OuroConfig(dtype=config["dtype"], **sizes_of(config))


def _engine(ctx):
    """The program under test, built as a user builds it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving import Engine, LoopedDecodeModel

    sizes = ctx.config["sizes"]
    ecfg, dtype = ctx.config["engine"], ctx.config["dtype"]
    t0 = time.perf_counter()
    params = ctx.reference().make_weights(sizes, ctx.seed, jnp.dtype(dtype))
    jax.block_until_ready(params)
    ctx.say(f"weights: seed {ctx.seed}, {dtype}, on the device in "
            f"{time.perf_counter() - t0:.2f}s")
    model = LoopedDecodeModel(model_config(ctx.config), params=params)
    eng = Engine(model, num_slots=ecfg["num_slots"],
                 num_pages=ecfg["num_pages"], page_size=ecfg["page_size"],
                 max_seq_len=ecfg["max_seq_len"],
                 max_queue=ecfg.get("max_queue", 256))
    _log_stats(ctx, eng)
    return eng, params


def _log_stats(ctx, eng):
    """Keep the loop's tallies of every `Engine.stats()` of the run (the
    first is read after warm-up, before the harness's step loop exists;
    the last after it has stopped), and read one more on each side of the
    traced span: the tallies are cumulative, so the readers take
    differences (readers/looped.py)."""
    log = ctx.stats_log = []
    stats = eng.stats

    def logged(at=""):
        out = stats()
        log.append({"at": at, "loop_passes": out.get("loop_passes"),
                    "exit_mass_share": out.get("exit_mass_share")})
        return out
    eng.stats = logged
    start, stop = ctx.start_trace, ctx.stop_trace

    def start_trace():
        logged("trace_start")
        start()

    def stop_trace():
        logged("trace_end")
        return stop()
    ctx.start_trace, ctx.stop_trace = start_trace, stop_trace


_run = types.FunctionType(
    serve.run.__code__, {**serve.run.__globals__, "_engine": _engine}, "run")


def run(ctx) -> dict:
    # first of all: a program without this model fails here, at once
    from paddle_tpu.serving import LoopedDecodeModel  # noqa: F401
    ctx.config["sizes"] = sizes_of(ctx.config)
    out = _run(ctx)
    out["stats_log"] = ctx.stats_log
    return out

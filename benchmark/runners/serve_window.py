"""Runner of a served configuration whose attention layers are of two kinds
(`paddle_tpu.serving.WindowedDecodeModel`: the full layer's K/V in pages
under a request's table, the sliding layers' in a ring of pages a slot;
routed experts beside a shared one). Everything but the engine's
builder and the sample is `runners/serve.py`'s run with `runners/
serve_hybrid.py`'s comparison: the experts are routed, so `correct` replays
the program's routing in the float32 reference (that module says why), and
the experts' tallies are logged round the traced span. The sample is drawn
from the greedy requests that handed their routing back so that it holds
what this cache can get wrong: requests whose rings have wrapped, and one
that never left the window. Bound as `serve_hybrid.py` binds its own
(PERF.md, Open questions: let `serve.run` take them as arguments in the
next `benchmark` PR).
"""
from __future__ import annotations

import time
import types

import numpy as np

from . import serve
from .serve_hybrid import _compare, _flag_routing, _log_stats

PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_hidden_layers", "layer_types",
    "num_dense_layers", "num_experts", "num_experts_per_tok",
    "num_shared_experts", "num_attention_heads", "num_key_value_heads",
    "head_dim", "sliding_window", "global_attn_every_n_layers", "rope_theta",
    "rms_norm_eps", "route_norm", "route_scale", "score_func", "mup_enabled",
    "n_group", "topk_group", "max_position_embeddings")
# published keys that say what this block does NOT have, or has in one
# form only: the program builds nothing for another value
FIXED = {"hidden_act": "silu", "tie_word_embeddings": False,
         "rope_scaling": None, "num_expert_groups": 1,
         "num_limited_groups": 1}

def sizes_of(config: dict) -> dict:
    """The sizes the program and the reference are built from: the
    published keys, which the configuration's file holds at its top level
    under the names `config.json` gives them, and the sizes assumed.
    `conv_L_cache` 1 (no convolution) so that `readers/hybrid.py::_fields`
    fills: the other names it indexes are published ones here."""
    for key, want in FIXED.items():
        if config.get(key) != want:
            raise ValueError(f"{key} = {config.get(key)!r}: only {want!r} "
                             f"is built")
    sizes = {k: config[k] for k in PUBLISHED}
    sizes.update(config.get("sizes_assumed", {}))
    sizes["conv_L_cache"] = 1
    return sizes


def model_config(config: dict):
    """The program's AfmoeConfig at the file's sizes and dtype."""
    from paddle_tpu.models.afmoe import AfmoeConfig
    s = sizes_of(config)
    s.pop("conv_L_cache")
    s["layer_types"] = tuple(s["layer_types"])
    return AfmoeConfig(dtype=config["dtype"], **s)


def _engine(ctx):
    """The program under test, built as a user builds it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving import Engine, WindowedDecodeModel

    sizes = ctx.config["sizes"]
    ecfg, dtype = ctx.config["engine"], ctx.config["dtype"]
    t0 = time.perf_counter()
    params = ctx.reference().make_weights(sizes, ctx.seed, jnp.dtype(dtype))
    jax.block_until_ready(params)
    ctx.say(f"weights: seed {ctx.seed}, {dtype}, on the device in "
            f"{time.perf_counter() - t0:.2f}s")
    model = WindowedDecodeModel(model_config(ctx.config), params=params)
    eng = Engine(model, num_slots=ecfg["num_slots"],
                 num_pages=ecfg["num_pages"], page_size=ecfg["page_size"],
                 max_seq_len=ecfg["max_seq_len"],
                 max_queue=ecfg.get("max_queue", 256))
    _flag_routing(eng)
    _log_stats(ctx, eng)
    ring = eng.stats()["pool"]["window"]
    ctx.say(f"pages: {ecfg['num_pages']} under the requests' tables; a ring "
            f"of {ring['ring_pages']} a slot for a window of "
            f"{ring['window']}")
    return eng, params


def _sample(ctx, done_in):
    """`serve._sample` over the greedy requests that handed their routing
    back, with the places after the longest given first to one more
    request whose context passed two windows (a ring of a window and a
    page has wrapped by then) and to one that stayed inside the window,
    where the window finished such requests."""
    have = [t for t in done_in if t.req.routing is not None
            and t.item["temperature"] == 0.0 and t.req.status == "done"]
    if not have:
        return []
    k = int(ctx.config["correct"]["sample_requests"])
    window = int(ctx.config["sizes"]["sliding_window"])
    total = lambda t: t.item["prompt_len"] + len(t.req.generated)
    have.sort(key=lambda t: t.item["index"])
    picks = [max(have, key=total)]
    rng = np.random.Generator(np.random.Philox(key=[ctx.seed, 11]))
    rest = [have[i] for i in rng.permutation(len(have))
            if have[i] is not picks[0]]
    for want in (lambda t: total(t) > 2 * window,
                 lambda t: total(t) < window):
        hit = next((t for t in rest if want(t)), None)
        if hit is not None and len(picks) < k:
            picks.append(hit)
            rest.remove(hit)
    picks += rest[:k - len(picks)]
    ctx.say("sample: contexts "
            + " ".join(str(total(t)) for t in picks)
            + f" (window {window})")
    return picks


_run = types.FunctionType(
    serve.run.__code__,
    {**serve.run.__globals__, "_engine": _engine, "_sample": _sample,
     "_compare": _compare}, "run")


def run(ctx) -> dict:
    # first of all: a program without this model fails here, at once
    from paddle_tpu.serving import WindowedDecodeModel  # noqa: F401
    ctx.config["sizes"] = sizes_of(ctx.config)
    out = _run(ctx)
    out["stats_log"] = ctx.stats_log
    return out

"""Runner of a served configuration whose model keeps two kinds of state
(`paddle_tpu.serving.HybridDecodeModel`: paged K/V, per-slot convolution
state, routed experts). Everything but the engine's builder and the
comparison with the reference is `runners/serve.py`'s: its `run` executes
here with `_engine`, `_sample` and `_compare` bound to this module's (it
reaches them as module globals; PERF.md, Open questions: let `serve.run`
take them as arguments in the next `benchmark` PR).

`correct` under the program's own routing. Top-k of the experts' scores is
discontinuous: at a near tie a sound bfloat16 program and the float32
reference choose different experts, and every later position inherits
the difference (PERF.md, Findings, PR 26, has the rate and the cost
measured on the chip). So a seeded half of the greedy requests is
submitted with `return_routing=True`, the sample is drawn from those, and
the reference (reference/lfm2_moe.py::replay) is given the experts the
program chose: it checks each of them against its own scores (the
shortfall: a wrong router fails here) and computes the logits with them
(the gap: what is left is precision alone).
"""
from __future__ import annotations

import time
import types

import numpy as np

from . import serve

PUBLISHED = (
    "vocab_size", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_hidden_layers", "layer_types",
    "num_dense_layers", "num_experts", "num_experts_per_tok",
    "num_attention_heads", "num_key_value_heads", "conv_L_cache",
    "rope_theta", "norm_eps", "norm_topk_prob", "use_expert_bias",
    "routed_scaling_factor", "max_position_embeddings")


def sizes_of(config: dict) -> dict:
    """The sizes the program and the reference are built from: the
    published keys, which the configuration's file holds at its top level
    under the names `config.json` gives them, and the sizes assumed."""
    sizes = {k: config[k] for k in PUBLISHED}
    sizes.update(config.get("sizes_assumed", {}))
    return sizes


def model_config(config: dict):
    """The program's LFM2Config at the file's sizes and dtype."""
    from paddle_tpu.models.lfm2 import LFM2Config
    s = sizes_of(config)
    s.pop("head_dim", None)         # derived there: hidden / heads
    return LFM2Config(dtype=config["dtype"], **s)


def _engine(ctx):
    """The program under test, built as a user builds it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving import Engine, HybridDecodeModel

    sizes = ctx.config["sizes"]
    ecfg, dtype = ctx.config["engine"], ctx.config["dtype"]
    t0 = time.perf_counter()
    params = ctx.reference().make_weights(sizes, ctx.seed, jnp.dtype(dtype))
    jax.block_until_ready(params)
    ctx.say(f"weights: seed {ctx.seed}, {dtype}, on the device in "
            f"{time.perf_counter() - t0:.2f}s")
    model = HybridDecodeModel(model_config(ctx.config), params=params)
    eng = Engine(model, num_slots=ecfg["num_slots"],
                 num_pages=ecfg["num_pages"], page_size=ecfg["page_size"],
                 max_seq_len=ecfg["max_seq_len"],
                 max_queue=ecfg.get("max_queue", 256))
    _flag_routing(eng)
    _log_stats(ctx, eng)
    return eng, params


def _flag_routing(eng):
    """Every second greedy request (by its seed) hands back its routing."""
    submit = eng.submit

    def flagged(prompt, max_new, **kw):
        want = kw.get("temperature", 0.0) == 0.0 \
            and int(kw.get("seed") or 0) % 2 == 0
        return submit(prompt, max_new, return_routing=want, **kw)
    eng.submit = flagged


def _log_stats(ctx, eng):
    """Keep every `Engine.stats()` of the run, and read one more on each
    side of the traced span: the experts' tallies are cumulative, so the
    readers take differences (readers/hybrid.py)."""
    log = ctx.stats_log = []
    stats = eng.stats

    def logged(at=""):
        out = stats()
        log.append({"at": at, "expert_tokens": out.get("expert_tokens"),
                    "expert_touched": out.get("expert_touched")})
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


def _sample(ctx, done_in):
    return serve._sample(ctx, [t for t in done_in
                               if t.req.routing is not None])


def replayed(ctx, params, sample, precision="f32", alt_precision=None,
             free=False):
    """For each sampled request, the reference run over its prompt and
    its served tokens with the experts the program chose (`free`: with its
    own). Returns a dict: `gap` (widest gap of a served token below the
    reference's best logit), `shortfall` (widest distance of a chosen
    expert's biased score below the reference's own k-th best), `flips`
    (share of the routed token-layers where the program's set is not the
    reference's), `tokens`, and with `alt_precision` `gap_alt`: the widest
    gap of the token that precision puts first at the same positions."""
    ref = ctx.reference()
    sizes = ctx.config["sizes"]
    T = int(ctx.config["correct"]["reference_length"])
    out = {"gap": 0.0, "gap_alt": 0.0, "shortfall": 0.0, "tokens": 0}
    flipped = routed = 0
    for t in sample:
        p, g = t.item["prompt_len"], len(t.req.generated)
        ids = np.zeros((T,), np.int32)
        ids[:p] = t.item["prompt"]
        ids[p:p + g] = t.req.generated
        routing = None if free else t.req.routing
        alt = None
        if alt_precision:
            best = ref.replay(params, ids, sizes, alt_precision,
                              routing=routing)[2]
            alt = np.concatenate([ids[:1], np.asarray(best)])
        gap, gap_alt, _, short = ref.replay(params, ids, sizes, precision,
                                            alt, routing)
        # position p-1 predicts the first served token, p+g-2 the last
        served = slice(p - 1, p + g - 1)
        out["gap"] = max(out["gap"], float(np.max(np.asarray(gap)[served])))
        out["gap_alt"] = max(out["gap_alt"],
                             float(np.max(np.asarray(gap_alt)[served])))
        if routing is not None:
            short = np.asarray(short)[:len(routing)]
            out["shortfall"] = max(out["shortfall"], float(np.max(short)))
            flipped += int(np.sum(short > 0))
            routed += short.size
        out["tokens"] += g
    out["flips"] = flipped / routed if routed else 0.0
    return out


def _compare(ctx, params, sample):
    if not sample:
        return
    c = ctx.config["correct"]
    t0 = time.perf_counter()
    got = replayed(ctx, params, sample, alt_precision=ctx.control)
    ctx.say(f"reference: {len(sample)} requests, {got['tokens']} served "
            f"tokens, {time.perf_counter() - t0:.1f}s; the program chose "
            f"another set of experts than the reference in "
            f"{100 * got['flips']:.3f}% of the routed token-layers")
    if ctx.control:
        free = replayed(ctx, params, sample, free=True)
        ctx.control_readings.update(
            gap=got["gap_alt"], free_routing_gap=free["gap"],
            flips=got["flips"])
        ctx.say(f"CONTROL {ctx.control}: widest gap of the token it puts "
                f"first below the reference's best logit, both under the "
                f"program's routing: {got['gap_alt']!r} (program: "
                f"{got['gap']!r}); the program against the reference's OWN "
                f"routing: {free['gap']!r}")
    ctx.check("widest gap of a served greedy token below the reference's "
              "best logit, under the program's routing", got["gap"],
              float(c["gap_limit"]))
    ctx.check("widest shortfall of a chosen expert's biased score below "
              "the reference's own k-th best", got["shortfall"],
              float(c["shortfall_limit"]))


_run = types.FunctionType(
    serve.run.__code__,
    {**serve.run.__globals__, "_engine": _engine, "_sample": _sample,
     "_compare": _compare}, "run")


def run(ctx) -> dict:
    # first of all: a program without this model fails here, at once
    from paddle_tpu.serving import HybridDecodeModel  # noqa: F401
    ctx.config["sizes"] = sizes_of(ctx.config)
    out = _run(ctx)
    out["stats_log"] = ctx.stats_log
    return out

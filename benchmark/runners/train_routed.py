"""Runner of a trained configuration whose feed-forward layers are routed
experts of which this rank holds a share (`paddle_tpu.models.mellum` handed
to `HybridParallelTrainStep`). The window, its clock and the three compared
steps are `runners/train.py`'s; what differs is bound here as
`serve_window.py` binds `serve.run`: the trainer is built with the model
(its parameters made on the device, the benchmark's put in their place),
the three steps hand back the experts they chose and the reference replays
them (top-8 of 64 is discontinuous: configs/mellum2_12b_a2p5b_train.json,
`why_replay`), the shortfall is compared beside the three norms, and the
trainer's tally of choices is read after the window (PERF.md, Open
questions: let `train.run` take these as arguments in the next `benchmark`
PR).
"""
from __future__ import annotations

import time
import types

import numpy as np

from . import train

PUBLISHED = (
    "vocab_size", "hidden_size", "moe_intermediate_size",
    "num_hidden_layers", "layer_types", "num_experts",
    "num_experts_per_tok", "norm_topk_prob", "num_attention_heads",
    "num_key_value_heads", "head_dim", "sliding_window", "rope_parameters",
    "rms_norm_eps", "max_position_embeddings", "experts_held")
# published keys that say what this block does NOT have, or has in one
# form only: the program builds nothing for another value
FIXED = {"hidden_act": "silu", "tie_word_embeddings": False,
         "attention_bias": False, "use_sliding_window": True,
         "max_window_layers": 0}


def sizes_of(config: dict) -> dict:
    """The sizes the program and the reference are built from: the
    published keys, which the configuration's file holds at its top level
    under the names `config.json` gives them, the share held and the sizes
    assumed. `head_dim` and `seq`-shaped names are what the accepted
    readers' patterns fill from."""
    for key, want in FIXED.items():
        if config.get(key) != want:
            raise ValueError(f"{key} = {config.get(key)!r}: only {want!r} "
                             f"is built")
    if set(config["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("only sparse layers are built")
    sizes = {k: config[k] for k in PUBLISHED}
    sizes.update(config.get("sizes_assumed", {}))
    return sizes


def model_config(config: dict, **kw):
    """The program's MellumConfig at the file's sizes and dtype."""
    from paddle_tpu.models.mellum import MellumConfig
    s = sizes_of(config)
    rp = s.pop("rope_parameters")
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    if sliding["rope_type"] != "default" or full["rope_type"] != "yarn" \
            or full["rope_theta"] != sliding["rope_theta"]:
        raise ValueError(f"rope_parameters {rp}: only default on the "
                         f"sliding layers and yarn on the full ones, one "
                         f"theta, is built")
    held = s.pop("experts_held")
    return MellumConfig(
        layer_types=tuple(s.pop("layer_types")),
        rope_theta=float(full["rope_theta"]),
        yarn_factor=float(full["factor"]),
        yarn_original_max_position_embeddings=int(
            full["original_max_position_embeddings"]),
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        experts_held=tuple(range(int(held))), amp_dtype=config["dtype"],
        **s, **kw)


def _trainer(ctx):
    """The program under test, built as a user builds it."""
    import jax
    from paddle_tpu.models.mellum import MellumTrainModel
    from paddle_tpu.parallel.hybrid import HybridParallelTrainStep

    tcfg, hyper = ctx.config["trainer"], ctx.config["optimizer"]
    t0 = time.perf_counter()
    step = HybridParallelTrainStep(
        MellumTrainModel(model_config(ctx.config,
                                      attn_impl=tcfg["attn_impl"],
                                      remat=tcfg["remat"])),
        pp=int(tcfg["pp"]), tp=int(tcfg["tp"]), dp=int(tcfg.get("dp", 1)),
        lr=hyper["lr"], weight_decay=hyper["weight_decay"],
        beta1=hyper["beta1"], beta2=hyper["beta2"],
        epsilon=hyper["epsilon"], grad_clip_norm=hyper["grad_clip_norm"],
        seed=ctx.seed % (2**31 - 1))
    jax.block_until_ready(step.params)
    t1 = time.perf_counter()
    step.params = train._weights(ctx, step, 1)
    jax.block_until_ready(step.params)
    ctx.say(f"trainer built in {t1 - t0:.1f}s (its own weights made on the "
            f"device); benchmark weights from seed {ctx.seed} put in their "
            f"place in {time.perf_counter() - t1:.2f}s")
    ctx.trainer = step
    return step


def first_steps(ctx, step, feed):
    """`train.first_steps`, and the experts each of the three steps chose
    (read here, outside the window, which leaves them unread)."""
    chosen = []

    class Keeping:
        """The trainer, with each call's choice kept."""
        params = property(lambda _s: step.params)
        opt_state = property(lambda _s: step.opt_state)

        def __call__(self, ids):
            loss = step(ids)
            chosen.append(np.asarray(step.last_chosen))
            return loss

    out = train.first_steps(ctx, Keeping(), feed)
    ctx.routing = chosen
    return out


def compare(ctx, got, want):
    """`train.compare`, and the widest shortfall of a chosen expert."""
    train.compare(ctx, got, want[:3])
    ctx.check("widest shortfall of a chosen expert's probability below "
              "the reference's own 8th best", float(want[3]),
              float(ctx.config["correct"]["shortfall_limit"]))


class _Replaying:
    """The reference with the program's routing given to `train_steps`."""

    def __init__(self, ref, ctx):
        self._ref, self._ctx = ref, ctx

    def __getattr__(self, name):
        return getattr(self._ref, name)

    def train_steps(self, params, batches, sizes, hyper, **kw):
        out = self._ref.train_steps(params, batches, sizes, hyper,
                                    routing=self._ctx.routing, **kw)
        if "precision" in kw:       # tools/probe.py --control
            self._ctx.control_readings["shortfall"] = float(out[3])
            self._ctx.say(f"CONTROL {kw['precision']}: shortfall "
                          f"{float(out[3])!r}")
        return out


class _Context:
    """The harness's context with the reference replaying and the tally
    read before the trainer is let go."""

    def __init__(self, ctx):
        object.__setattr__(self, "_ctx", ctx)

    def __getattr__(self, name):
        return getattr(self._ctx, name)

    def __setattr__(self, name, value):
        setattr(self._ctx, name, value)

    def reference(self):
        return _Replaying(self._ctx.reference(), self._ctx)

    def release(self):
        ctx = self._ctx
        ctx.tally = ctx.trainer.tally_stats()
        ctx.trainer = None
        ctx.release()


_run = types.FunctionType(
    train.run.__code__,
    {**train.run.__globals__, "_trainer": _trainer,
     "first_steps": first_steps, "compare": compare}, "run")


def run(ctx) -> dict:
    # first of all: a program without this model fails here, at once
    from paddle_tpu.models.mellum import MellumTrainModel  # noqa: F401
    ctx.config["sizes"] = sizes_of(ctx.config)
    out = _run(_Context(ctx))
    out["tally"] = ctx.tally
    t = ctx.tally
    ctx.say(f"tally: {t['pairs_routed']} pairs routed, {t['pairs_held']} "
            f"held here ({100.0 * t['pairs_held'] / t['pairs_routed']:.3f}"
            f"%)")
    return out

"""HybridParallelTrainStep: training over a (dp, pp, tp) mesh of the model
it is handed (GPT by its GPTConfig, over every axis; any other model by the
four things docs/TRAINING.md lists, on the axes that model says it runs).

The TPU-native hybrid-parallel engine consumed by
`fleet.distributed_optimizer` when `DistributedStrategy.pipeline` /
`tensor_parallel` are on (reference chain: fluid PipelineOptimizer
optimizer.py:3666 + fleet meta_optimizers/pipeline_optimizer.py:24; TP has
no reference equivalent — SURVEY SS2.9 mandates a fresh pjit design).

One jitted step = fwd (+ pipeline schedule) + bwd + AdamW update:
  * dp: batch dim sharded; grad psum implicit in sharded autodiff.
  * tp: megatron-style PartitionSpecs on params (models/gpt.py
    `gpt_param_specs`); GSPMD partitions matmuls and inserts collectives.
  * pp: stacked per-stage block params + scan/ppermute GPipe
    (parallel/pipeline.py); autodiff yields the reverse schedule.
Optimizer state is sharded exactly like its param (ZeRO-free but
TP/PP-partitioned), donated every step.
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
from functools import partial
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import gpt as G
from ..observability import tracing as _tracing
from .pipeline import pipeline_apply
from .sharding import _restrict, kernel_mesh

__all__ = ["HybridParallelTrainStep", "make_hybrid_mesh"]

logger = logging.getLogger("paddle_tpu.parallel.hybrid")

_DECAY = {"wte", "wpe", "wq", "wk", "wv", "wo", "w_up", "w_down",
          "we_up", "we_down"}


def make_hybrid_mesh(dp: int = 1, pp: int = 1, tp: int = 1, sp: int = 1,
                     ep: int = 1, devices=None) -> Mesh:
    """("pp","dp","sp","ep","tp") mesh — tp innermost so its collectives
    ride the fastest ICI links; ep next (MoE all_to_all dispatch); sp next
    (ring attention's ppermute hops); pp outermost (cheapest traffic: one
    activation per microbatch tick).

    Devices are taken in jax.devices() id order. On the four-chip v5e
    host (2x2, no wrap-around) ids 0..3 sit at (0,0) (1,0) (0,1) (1,1),
    so with pp=2 x tp=2 each tp pair and each pp pair is a pair of
    physical neighbours. Larger slices need a topology-aware order."""
    devs = np.array(devices if devices is not None else jax.devices())
    n = dp * pp * tp * sp * ep
    if devs.size < n:
        raise ValueError(f"need {n} devices, have {devs.size}")
    return Mesh(devs[:n].reshape(pp, dp, sp, ep, tp),
                ("pp", "dp", "sp", "ep", "tp"))


class _GPTModel:
    """GPT as the trainer's first model: what `HybridParallelTrainStep`
    asks of any (docs/TRAINING.md). Its loss is the trainer's own
    `loss_fn` (the pipeline, ring and expert contexts are the trainer's);
    its parameters are drawn on the host, as they always were."""

    decay = _DECAY
    has_aux = False

    def __init__(self, trainer):
        self._t = trainer

    def param_specs(self):
        t = self._t
        return G.gpt_param_specs(pp_stacked=t.pp > 1,
                                 moe=t.cfg.num_experts > 0)

    def init_params(self, seed, shardings):
        t = self._t
        params = jax.tree_util.tree_map(jnp.asarray,
                                        G.init_gpt_params(t.cfg, seed))
        if t.pp > 1:
            lps = t.cfg.num_layers // t.pp
            params["blocks"] = {
                k: v.reshape(t.pp, lps, *v.shape[1:])
                for k, v in params["blocks"].items()}
        return jax.tree_util.tree_map(jax.device_put, params, shardings)

    def loss(self, params, ids, key=None):
        return self._t.loss_fn(params, ids, key)


class HybridParallelTrainStep:
    """step(ids[B, T]) -> loss; B must divide by dp (and by
    n_microbatches*dp when pp>1).

    `model`: a `GPTConfig`, or an object with `param_specs()`,
    `init_params(seed, shardings)` (made on the device), `decay` (names of
    the leaves AdamW decays), `loss(params, ids, key)` and `parallel_axes`
    (which of pp, tp, sp, ep it runs above 1; `refuse(axis, size)` says
    what the others lack). A model with `has_aux` returns (loss,
    {"chosen": int32 [layers, tokens, k]}): the step keeps the last one
    (`last_chosen`, unread) and a tally of choices by expert
    (`tally_stats()`)."""

    def __init__(self, model, mesh: Mesh | None = None,
                 dp: int = 1, pp: int = 1, tp: int = 1, sp: int = 1,
                 ep: int = 1, n_microbatches: int | None = None, lr=1e-4,
                 weight_decay: float = 0.01, beta1: float = 0.9,
                 beta2: float = 0.999, epsilon: float = 1e-8,
                 grad_clip_norm: float | None = 1.0, seed: int = 0,
                 sharding: bool = False, devices=None,
                 pipeline_schedule: str = "1F1B"):
        if mesh is None:
            mesh = make_hybrid_mesh(dp, pp, tp, sp, ep, devices)
        if not isinstance(model, G.GPTConfig):
            self._init_handed_in(model, mesh, (
                lr, weight_decay, beta1, beta2, epsilon, grad_clip_norm,
                seed))
            return
        cfg = model
        self.sp = mesh.shape.get("sp", 1)
        self.pp = mesh.shape.get("pp", 1)
        self.ep = mesh.shape.get("ep", 1)
        # reference schedule_mode values: "1F1B" (SectionWorker interleave,
        # here parallel/pipeline_1f1b.py) and "F-then-B" (GPipe, here the
        # differentiable scan in parallel/pipeline.py)
        if pipeline_schedule not in ("1F1B", "F-then-B", "gpipe"):
            raise ValueError(f"unknown pipeline_schedule "
                             f"{pipeline_schedule!r}")
        self._schedule = "1F1B" if pipeline_schedule == "1F1B" else "gpipe"
        if self.ep > 1 and cfg.num_experts <= 0:
            raise ValueError("ep>1 needs a MoE model (cfg.num_experts>0)")
        if cfg.num_experts > 0:
            if self.pp > 1 and self._schedule != "1F1B":
                raise NotImplementedError(
                    "MoE x pipeline needs schedule_mode='1F1B' (the GPipe "
                    "scan drops the per-layer load-balance aux; the 1F1B "
                    "engine threads it through each stage's vjp)")
            if self.ep > 1 and cfg.num_experts % self.ep:
                raise ValueError(
                    f"num_experts={cfg.num_experts} not divisible by "
                    f"ep={self.ep}")
        if self.sp > 1:
            if self.pp > 1 and self._schedule != "1F1B":
                raise NotImplementedError(
                    "sp x pp needs schedule_mode='1F1B': the ring "
                    "attention rides INSIDE the 1F1B stage functions "
                    "(sp stays a GSPMD axis with the ring's shard_map "
                    "nested in the pp-manual region); the GPipe scan "
                    "has no per-stage function to host it")
            # sequence parallel => ring attention over the sp axis
            cfg = dataclasses.replace(cfg, attn_impl="ring")
        tp = mesh.shape.get("tp", 1)
        if cfg.attn_impl == "flash" and cfg.num_heads % tp:
            raise ValueError(
                f"attn_impl='flash' runs the kernel on each tp shard's "
                f"heads: num_heads={cfg.num_heads} must divide by tp={tp} "
                f"(attn_impl='xla' has no such limit)")
        if mesh.size > 1 and cfg.fused_blocks and cfg.num_experts == 0:
            # jax does not partition a Mosaic kernel (see
            # sharding.kernel_mesh). Flash attention runs per shard (the
            # step is traced under kernel_mesh); the fused decoder-tail
            # kernels cannot: under tp `wo` and `w_down` are row-parallel,
            # so the sum they feed the fused LayerNorm / residual is
            # partial on each shard. They are switched off here, where
            # the mesh is known — the gate would time them on one device,
            # let them win, and the step would then fail to lower.
            logger.warning(
                "fused_blocks is off on this %d-device mesh %s: the fused "
                "decoder-tail kernels (ops/pallas_block.py) cannot be "
                "partitioned; the composed XLA tail runs instead",
                mesh.size, dict(mesh.shape))
            cfg = dataclasses.replace(cfg, fused_blocks=False)
        self.cfg = cfg
        self.mesh = mesh
        self.n_micro = n_microbatches or max(2 * self.pp, 1)
        if self.pp > 1 and cfg.dropout and self._schedule != "1F1B":
            raise NotImplementedError(
                "pipeline dropout needs schedule_mode='1F1B' (its stage "
                "functions re-derive per-(stage, microbatch) rng keys; the "
                "GPipe scan carries no rng)")
        if cfg.num_layers % self.pp:
            raise ValueError(
                f"num_layers={cfg.num_layers} not divisible by pp={self.pp}")
        self._take(_GPTModel(self), mesh, sharding, (
            lr, weight_decay, beta1, beta2, epsilon, grad_clip_norm, seed))

    def _init_handed_in(self, model, mesh, hyper):
        """A model other than GPT: the axes it does not run are refused by
        name, the rest is `_take`."""
        for axis in ("dp", "pp", "tp", "sp", "ep"):
            n = mesh.shape.get(axis, 1)
            if n > 1 and axis not in getattr(model, "parallel_axes", ()):
                if hasattr(model, "refuse"):
                    model.refuse(axis, n)
                raise NotImplementedError(
                    f"{type(model).__name__} does not run {axis}={n}")
        self.sp = self.pp = self.ep = 1
        self._schedule = "gpipe"
        self.cfg = getattr(model, "cfg", None)
        self.mesh = mesh
        self.n_micro = 1
        self._take(model, mesh, False, hyper)

    def _take(self, model, mesh, sharding, hyper):
        """The seam: parameters, their placement, which leaves decay and
        the loss all come from `model`."""
        lr, weight_decay, beta1, beta2, epsilon, grad_clip_norm, seed = hyper
        self.model = model
        self._lr = lr
        self._seed = seed
        self._hyper = dict(beta1=beta1, beta2=beta2, epsilon=epsilon)
        self._wd = weight_decay
        self._clip = grad_clip_norm

        self._specs = jax.tree_util.tree_map(
            lambda s: _restrict(s, mesh), model.param_specs(),
            is_leaf=lambda s: isinstance(s, P))
        self._shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), self._specs,
            is_leaf=lambda s: isinstance(s, P))
        self.params = model.init_params(seed, self._shardings)
        # a leaf decays by its own name, whatever it is nested in
        self._decays = [path[-1].key in model.decay for path, _leaf in
                        jax.tree_util.tree_flatten_with_path(self.params)[0]]
        # ZeRO-1 (strategy.sharding): optimizer moments shard over the dp
        # axis on a free divisible dim — each dp rank owns 1/dp of the
        # Adam state and computes its slice of the update; GSPMD inserts
        # the param all-gather (reference sharding/ZeRO stage-1
        # semantics, fleet sharding_configs)
        self.zero_sharding = bool(sharding) and mesh.shape.get("dp", 1) > 1

        def _opt_sharding(v, spec):
            if not self.zero_sharding:
                return NamedSharding(mesh, spec)
            ndp = mesh.shape["dp"]
            entries = list(spec) + [None] * (v.ndim - len(spec))
            for i in range(v.ndim):
                if entries[i] is None and v.shape[i] % ndp == 0:
                    entries[i] = "dp"
                    break
            return NamedSharding(mesh, P(*entries))

        self._opt_shardings = jax.tree_util.tree_map(
            lambda v, s: {"m1": _opt_sharding(v, s),
                          "m2": _opt_sharding(v, s)},
            self.params, self._specs,
            is_leaf=lambda s: isinstance(s, P))
        self.opt_state = jax.tree_util.tree_map(
            lambda v, sh: {"m1": jax.device_put(
                               jnp.zeros(v.shape, jnp.float32), sh["m1"]),
                           "m2": jax.device_put(
                               jnp.zeros(v.shape, jnp.float32), sh["m2"])},
            self.params, self._opt_shardings)
        repl = NamedSharding(mesh, P())
        self._pows = (jax.device_put(jnp.ones((1,), jnp.float32), repl),
                      jax.device_put(jnp.ones((1,), jnp.float32), repl))
        self._batch_sharding = NamedSharding(
            mesh, P("dp", "sp") if self.sp > 1 else P("dp"))
        self.last_chosen = None
        self._tally = None
        if getattr(model, "has_aux", False):
            # choices by layer and expert; by layer, the steps whose held
            # pairs passed the expert layer's bound on its sorted rows
            self._tally = jax.device_put(
                {"chosen": jnp.zeros((model.tally_layers, model.num_experts),
                                     jnp.int32),
                 "over_bound": jnp.zeros((model.tally_layers,), jnp.int32)},
                repl)
        self._jit_step = self._build(mesh)

    # ------------------------------------------------------------------
    def _trace_contexts(self):
        """What model code consults while this step is traced."""
        stack = contextlib.ExitStack()
        if self.mesh.size > 1:
            stack.enter_context(kernel_mesh(self.mesh))
        if self.cfg.num_experts > 0:
            from .moe import moe_context
            stack.enter_context(moe_context(self.mesh, "ep"))
        return stack

    def loss_fn(self, params, ids, key=None):
        with self._trace_contexts():
            return self._loss_inner(params, ids, key)

    def _loss_inner(self, params, ids, key=None):
        cfg, mesh = self.cfg, self.mesh
        if self.sp > 1:
            from .sequence_parallel import ring_context
            ids = jax.lax.with_sharding_constraint(
                ids, NamedSharding(mesh, P("dp", "sp")))
            with ring_context(mesh, "sp"):
                return G.gpt_loss(params, ids, cfg, key=key)
        if self.pp == 1:
            return G.gpt_loss(params, ids, cfg, key=key)
        M = self.n_micro
        B, T = ids.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        x = G._embed(params, ids, cfg)
        x = x.reshape(M, B // M, T, cfg.hidden_size)
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(None, "dp")))
        def stage_fn(blk, h):
            out, _ = jax.lax.scan(G.block_body(cfg), h, blk)
            return out

        out = pipeline_apply(stage_fn, params["blocks"], x, mesh, "pp")
        out = out.reshape(B, T, cfg.hidden_size)
        logits = G._head(params, out, cfg)
        return G.gpt_loss(params, ids, cfg, logits=logits)

    # ------------------------------------------------------------------
    def _loss_and_grads_1f1b(self, params, ids, key):
        """pp>1 1F1B path: loss/grads come from the schedule engine
        (parallel/pipeline_1f1b.py), not from differentiating the forward;
        the embedding is kept under outer autodiff via jax.vjp and its
        cotangent routed from stage 0's input grads."""
        cfg, mesh = self.cfg, self.mesh
        M = self.n_micro
        B, T = ids.shape
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        ids_mb = ids.reshape(M, B // M, T)
        lps = cfg.num_layers // self.pp
        use_drop = bool(cfg.dropout) and key is not None
        from .pipeline_1f1b import pipeline_1f1b_grads
        n_auto = sum(1 for ax in ("dp", "tp", "sp", "ep")
                     if mesh.shape.get(ax, 1) > 1)
        if n_auto >= 2:
            # see the partitioner-workaround comment below: the embedding
            # table is consumed replicated throughout this step (its grad
            # is resharded to the tp spec by the jit out_shardings), and
            # the per-layer jax.checkpoint inside the stage scan is
            # dropped (also a partitioner trigger on this combo) — the
            # 1F1B engine already remats at stage granularity, so only
            # the within-B-tick residual footprint grows
            cfg = dataclasses.replace(cfg, remat=False)
            params = dict(params)
            params["wte"] = jax.lax.with_sharding_constraint(
                params["wte"], NamedSharding(mesh, P()))

        def emb_fn(embp):
            x = jnp.take(embp["wte"], ids_mb, axis=0) + embp["wpe"][:T]
            if cfg.amp_dtype:
                x = x.astype(jnp.dtype(cfg.amp_dtype))
            if use_drop:
                x = G._dropout(x, cfg.dropout,
                               jax.random.fold_in(key, 0x5eed))
            return x

        embp = {"wte": params["wte"], "wpe": params["wpe"]}
        x0, emb_vjp = jax.vjp(emb_fn, embp)
        x0 = jax.lax.with_sharding_constraint(
            x0, NamedSharding(mesh, P(None, "dp", "sp")
                              if self.sp > 1 else P(None, "dp")))

        def stage_fn(local, x, k):
            if use_drop:
                lkeys = jax.random.split(k, lps)
                y, auxs = jax.lax.scan(G.block_body_keyed(cfg), x,
                                       (local, lkeys))
            else:
                y, auxs = jax.lax.scan(G.block_body(cfg), x, local)
            return y, jnp.sum(auxs)

        def last_fn(local, sh, x, ids_one, k):
            y, aux = stage_fn(local, x, k)
            logits = G._head({"wte": sh["wte"], "lnf_s": sh["lnf_s"],
                              "lnf_b": sh["lnf_b"]}, y, cfg)
            loss = G.gpt_loss(None, ids_one, cfg, logits=logits)
            return y, loss, aux

        # XLA's SPMD partitioner Check-fails (spmd_partitioner_util.cc
        # group bookkeeping) when TWO auto mesh axes (e.g. dp and tp) are
        # active beside the manual pp axis and either (a) lax.cond
        # branches carry tp collectives or (b) the tp-vocab-sharded head
        # matmul sits inside the manual region. For that combo: run the
        # cond-free uniform executor (blocks+head every B-tick, cotangent-
        # masked) AND consume the embedding/head table replicated (one
        # wte all-gather per step, applied above). Verified exact-loss/
        # grad parity vs the sharded-head cond executor on
        # single-auto-axis meshes.
        shared = {"wte": params["wte"], "lnf_s": params["lnf_s"],
                  "lnf_b": params["lnf_b"]}
        aux_w = cfg.moe_aux_weight if cfg.num_experts > 0 else 0.0
        ring_cm = contextlib.nullcontext()
        if self.sp > 1:
            # sp x pp: the sequence stays a GSPMD ("auto") axis inside
            # the pp-manual region; attention drops into the ring's own
            # shard_map over "sp" NESTED in the 1F1B engine's manual
            # region — the manual axes sets are disjoint, which jax's
            # shard_map supports
            from .sequence_parallel import ring_context
            ring_cm = ring_context(mesh, "sp")
        with ring_cm:
            loss, gblocks, gshared, dx0 = pipeline_1f1b_grads(
                stage_fn, last_fn, params["blocks"], shared, x0, ids_mb,
                mesh, "pp", aux_weight=aux_w, key=key,
                uniform_last=n_auto >= 2,
                uniform_all=self.sp > 1)
        (gemb,) = emb_vjp(dx0)
        grads = {"wte": gshared["wte"] + gemb["wte"].astype(jnp.float32),
                 "wpe": gemb["wpe"].astype(jnp.float32),
                 "lnf_s": gshared["lnf_s"], "lnf_b": gshared["lnf_b"],
                 "blocks": gblocks}
        return loss, grads

    def _build(self, mesh):
        from ..fluid import registry
        opdef = registry.require("adamw")
        hyper = dict(self._hyper)
        opdef.fill_default_attrs(hyper)
        wd, clip = self._wd, self._clip
        decays = self._decays
        use_1f1b = self.pp > 1 and self._schedule == "1F1B"

        def grads_1f1b(params, ids, key):
            with self._trace_contexts():
                return self._loss_and_grads_1f1b(params, ids, key)

        @jax.named_scope("adamw")
        def apply_update(params, opt_state, pows, grads, lr):
            if clip:
                leaves = jax.tree_util.tree_leaves(grads)
                gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(
                    jnp.float32))) for g in leaves))
                scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
                grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
            lr_arr = jnp.asarray([lr], jnp.float32)
            b1p, b2p = pows

            def upd(p, g, st, decay):
                ins = {"Param": [p], "Grad": [g], "LearningRate": [lr_arr],
                       "Moment1": [st["m1"]], "Moment2": [st["m2"]],
                       "Beta1Pow": [b1p], "Beta2Pow": [b2p]}
                attrs = dict(hyper)
                attrs["coeff"] = wd if decay else 0.0
                outs = opdef.compute(None, ins, attrs)
                return (outs["ParamOut"][0],
                        {"m1": outs["Moment1Out"][0],
                         "m2": outs["Moment2Out"][0]},
                        outs["Beta1PowOut"][0], outs["Beta2PowOut"][0])

            flat_p, tdef = jax.tree_util.tree_flatten(params)
            flat_g = jax.tree_util.tree_leaves(grads)
            flat_s = tdef.flatten_up_to(opt_state)
            new_p, new_s = [], []
            for p, g, st, decay in zip(flat_p, flat_g, flat_s, decays):
                np_, ns_, b1n, b2n = upd(p, g, st, decay)
                new_p.append(np_)
                new_s.append(ns_)
            return (jax.tree_util.tree_unflatten(tdef, new_p),
                    jax.tree_util.tree_unflatten(tdef, new_s),
                    (b1n, b2n))

        repl = NamedSharding(mesh, P())
        if use_1f1b:
            # TWO dispatches: the schedule+grads program, then the
            # clip+AdamW program. Fusing them into one jit Check-fails
            # XLA's SPMD partitioner when the pipeline's manual region,
            # dropout rng and the global-norm reduction meet on a
            # multi-auto-axis mesh; split programs compile clean and the
            # extra dispatch is noise next to a pipeline step.
            jit_grads = jax.jit(grads_1f1b, out_shardings=None)
            jit_update = jax.jit(
                apply_update, donate_argnums=(0, 1, 2, 3),
                out_shardings=(self._shardings, self._opt_shardings,
                               (repl, repl)))

            def step2(params, opt_state, pows, ids, lr, key):
                loss, grads = jit_grads(params, ids, key)
                new_p, new_s, new_pows = jit_update(params, opt_state,
                                                    pows, grads, lr)
                return loss, new_p, new_s, new_pows

            step2._jit_grads = jit_grads      # introspection (tests)
            step2._jit_update = jit_update
            return step2

        model = self.model
        if self._tally is not None:
            def step_aux(params, opt_state, pows, tally, ids, lr, key):
                (loss, aux), grads = jax.value_and_grad(
                    model.loss, has_aux=True)(params, ids, key)
                new_p, new_s, new_pows = apply_update(
                    params, opt_state, pows, grads, lr)
                chosen = aux["chosen"]              # [layers, tokens, k]
                hit = jax.vmap(lambda c: jnp.bincount(
                    c.reshape(-1), length=model.num_experts))(
                        chosen).astype(jnp.int32)
                over = jnp.sum(hit[:, jnp.asarray(model.experts_held)],
                               axis=1) > self._rows_bound(chosen.shape)
                return (loss, new_p, new_s, new_pows,
                        {"chosen": tally["chosen"] + hit,
                         "over_bound": tally["over_bound"]
                         + over.astype(jnp.int32)}, chosen)

            return jax.jit(
                step_aux, donate_argnums=(0, 1, 2, 3),
                out_shardings=(repl, self._shardings, self._opt_shardings,
                               (repl, repl), repl, repl))

        def step(params, opt_state, pows, ids, lr, key):
            loss, grads = jax.value_and_grad(model.loss)(
                params, ids, key)
            new_p, new_s, new_pows = apply_update(params, opt_state, pows,
                                                  grads, lr)
            return loss, new_p, new_s, new_pows

        return jax.jit(
            step, donate_argnums=(0, 1, 2),
            out_shardings=(repl, self._shardings, self._opt_shardings,
                           (repl, repl)))

    # ------------------------------------------------------------------
    def __call__(self, ids):
        """Hand one step to the device and return its loss unread.
        `train.step` is the host's part of a step (the batch's transfer
        in `train.put`, the jitted call in `train.dispatch`, the step's
        key between them); when the step completes is the caller's to
        observe."""
        self._step_no = getattr(self, "_step_no", 0) + 1
        with _tracing.span("train.step", step=self._step_no):
            with _tracing.span("train.put"):
                ids = jax.device_put(jnp.asarray(ids), self._batch_sharding)
            lr = self._lr() if callable(self._lr) else float(self._lr)
            key = jax.random.fold_in(jax.random.PRNGKey(self._seed),
                                     self._step_no)
            with _tracing.span("train.dispatch"):
                if self._tally is not None:
                    (loss, self.params, self.opt_state, self._pows,
                     self._tally, self.last_chosen) = self._jit_step(
                        self.params, self.opt_state, self._pows,
                        self._tally, ids, np.float32(lr), key)
                else:
                    loss, self.params, self.opt_state, self._pows = \
                        self._jit_step(self.params, self.opt_state,
                                       self._pows, ids, np.float32(lr), key)
        return loss

    def _rows_bound(self, chosen_shape) -> int:
        """The expert layer's bound on its sorted rows
        (`moe.held_rows_bound`) at a step's [layers, tokens, k]."""
        from .moe import held_rows_bound
        _layers, tokens, k = chosen_shape
        return held_rows_bound(tokens, k, len(self.model.experts_held),
                               self.model.num_experts)

    def tally_stats(self):
        """The routed model's counters since the trainer was built, read
        from the device (it waits for the steps in flight): token-expert
        pairs routed, pairs whose expert is held here, each held expert's
        count by layer; the steps taken, the expert layer's bound on the
        rows of its sorted arrays (None before the first step) and, by
        layer, the steps whose held pairs passed it (which took the
        whole-size path). None for a model that routes nothing."""
        if self._tally is None:
            return None
        tally = np.asarray(self._tally["chosen"])
        held = list(self.model.experts_held)
        return {"pairs_routed": int(tally.sum()),
                "pairs_held": int(tally[:, held].sum()),
                "experts_held": held,
                "held_counts": tally[:, held].tolist(),
                "steps": getattr(self, "_step_no", 0),
                "rows_bound": None if self.last_chosen is None
                else self._rows_bound(self.last_chosen.shape),
                "layer_steps_over_bound":
                    np.asarray(self._tally["over_bound"]).tolist()}

    def unstacked_params(self):
        """Params with block leaves back at [L, ...] (for parity checks /
        checkpoint export)."""
        p = jax.tree_util.tree_map(lambda x: x, self.params)
        if self.pp > 1:
            p["blocks"] = {k: v.reshape(-1, *v.shape[2:])
                           for k, v in p["blocks"].items()}
        return p

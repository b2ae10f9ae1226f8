"""GPipe pipeline parallelism as one differentiable XLA computation.

The reference implements PP by splitting the ProgramDesc into per-device
section programs (fluid/optimizer.py:3790 `_split_program`) executed by
SectionWorker threads streaming microbatches through blocking queues
(framework/device_worker.h:415).  On TPU the whole schedule becomes a single
SPMD computation instead: every stage's weights live on its "pp" mesh slice,
a `lax.scan` steps the clock, and `lax.ppermute` rotates activations around
the stage ring.  `jax.grad` differentiates straight through the scan +
ppermute, which *is* the reverse pipeline schedule — no hand-written 1F1B
bookkeeping, no host threads, no queues.

The shard_map is partial-manual: only the "pp" axis is manual; data- and
tensor-parallel axes stay in GSPMD "auto" mode, so the per-stage compute is
still partitioned over dp/tp by XLA.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["pipeline_apply", "num_ticks"]


def num_ticks(n_micro: int, n_stages: int) -> int:
    """GPipe clock length: M microbatches through S stages."""
    return n_micro + n_stages - 1


def pipeline_apply(stage_fn: Callable, stage_params: Any, mb_inputs,
                   mesh, axis_name: str = "pp"):
    """Run microbatches through a ring of pipeline stages.

    Args:
      stage_fn: (params_leafslice, x) -> y with y.shape == x.shape; applies
        one stage's worth of layers. Runs under GSPMD for non-pp axes.
      stage_params: pytree whose leaves are stacked per-stage [S, ...] and
        sharded P(axis_name, ...) on dim 0.
      mb_inputs: [M, mb, ...] microbatched activations, replicated over pp
        (other dims may be dp/tp-sharded; GSPMD keeps them sharded inside).
      mesh: jax.sharding.Mesh containing axis_name.
      axis_name: the pipeline mesh axis.

    Returns:
      [M, mb, ...] outputs of the final stage (same sharding as mb_inputs).
    """
    n_stages = mesh.shape[axis_name]
    n_micro = mb_inputs.shape[0]
    if n_stages == 1:
        params0 = jax.tree_util.tree_map(lambda x: x[0], stage_params)

        def body(carry, x):
            return carry, stage_fn(params0, x)

        _, out = jax.lax.scan(body, 0, mb_inputs)
        return out
    if n_micro < n_stages:
        raise ValueError(
            f"need microbatches >= pipeline stages, got {n_micro} < "
            f"{n_stages} (bubble would dominate; reference asserts the same "
            f"in PipelineOptimizer)")

    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def spmd(params, mbs):
        stage = jax.lax.axis_index(axis_name)
        local = jax.tree_util.tree_map(lambda x: x[0], params)
        state = jnp.zeros_like(mbs[0])
        outbuf = jnp.zeros_like(mbs)

        def tick(carry, t):
            state, outbuf = carry
            inject = mbs[jnp.minimum(t, n_micro - 1)]
            x = jnp.where(stage == 0, inject, state)
            y = stage_fn(local, x)
            # final stage completes microbatch t-(S-1) at tick t
            om = t - (n_stages - 1)
            is_out = jnp.logical_and(stage == n_stages - 1, om >= 0)
            upd = jax.lax.dynamic_update_index_in_dim(
                outbuf, y, jnp.maximum(om, 0), 0)
            outbuf = jnp.where(is_out, upd, outbuf)
            state = jax.lax.ppermute(y, axis_name, perm)
            return (state, outbuf), None

        (state, outbuf), _ = jax.lax.scan(
            tick, (state, outbuf), jnp.arange(num_ticks(n_micro, n_stages)))
        # only the last stage's buffer is real; stack stages and let the
        # caller's slice of [-1] compile to a plain shard read
        return outbuf[None]

    stacked = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(axis_name),
        axis_names=frozenset({axis_name}),
        check_vma=False,
    )(stage_params, mb_inputs)
    return stacked[-1]

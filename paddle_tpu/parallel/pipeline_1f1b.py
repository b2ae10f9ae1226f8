"""1F1B pipeline schedule as one lockstep SPMD computation.

The reference's 1F1B is a host-side thread schedule: SectionWorker threads
per stage pull microbatches from blocking queues and interleave one forward
with one backward so only ~pp microbatch activations stay live
(/root/reference/paddle/fluid/framework/device_worker.h:415,
/root/reference/python/paddle/fluid/optimizer.py:3666 PipelineOptimizer).

On TPU the schedule becomes data: a trace-time event simulator
(`simulate_1f1b`) produces, for every clock tick and stage, which action
(Forward on microbatch i / Backward on microbatch j / idle) the stage takes
and which buffer slots it touches.  A `lax.scan` steps the clock inside a
`shard_map` that is manual only over the "pp" axis (dp/tp/sp stay in GSPMD
auto mode), `lax.ppermute` moves activations forward and cotangents
backward each tick, and `lax.cond` masks the idle slots.

Backward is **rematerialised**: a stage stores only its per-microbatch
*inputs* (at most pp in flight, the 1F1B bound) and re-runs the stage
forward inside `jax.vjp` at its B-tick — the GPipe-by-autodiff engine in
parallel/pipeline.py instead stashes every residual of all M microbatches.
The last stage owns head+loss, so each microbatch's cotangent seeds as soon
as its activations arrive — no full-batch forward barrier.

Because grads are produced *by the schedule itself* (not by differentiating
it), the public entry returns (loss, block-grads, shared-grads, d(input));
`HybridParallelTrainStep` splices those into the same clip/Adam update used
by the autodiff paths and routes the embedding cotangent through an outer
`jax.vjp` of the (cheap) embed.

Dropout is supported: per-(stage, microbatch) keys are re-derived with
`jax.random.fold_in` at both F- and B-ticks, so the rematerialised backward
sees the identical masks (this is what lifts the GPipe path's dropout=0
restriction).  MoE load-balance aux flows too: each stage's B returns its
per-microbatch aux and its cotangent seeds with aux_weight/M — lifting the
MoE x pp restriction.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["simulate_1f1b", "pipeline_1f1b_grads"]


# ---------------------------------------------------------------------------
# trace-time schedule simulation
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Schedule:
    """Static per-tick schedule tables, each [n_ticks, n_stages] int32.

    f_on/f_micro/f_slot: forward action (slot = x-buffer slot to read;
      stage 0 reads the resident microbatch inputs instead).
    b_on/b_micro/b_xslot/b_dxslot: backward action (xslot = stored input,
      dxslot = arrived cotangent; the last stage seeds its own cotangent).
    recv_on/recv_slot: an activation permuted in at the END of tick t-1 is
      committed into the x-buffer at the START of tick t.
    drecv_on/drecv_slot: same for cotangents.
    """
    n_ticks: int
    n_xslots: int
    n_dxslots: int
    f_on: Any; f_micro: Any; f_slot: Any
    b_on: Any; b_micro: Any; b_xslot: Any; b_dxslot: Any
    recv_on: Any; recv_slot: Any
    drecv_on: Any; drecv_slot: Any


def simulate_1f1b(n_stages: int, n_micro: int,
                  both_per_tick: bool = False) -> Schedule:
    """Event-driven lockstep 1F1B: B-priority, one-tick communication
    latency, the last stage runs no separate forward (its B rematerialises
    blocks+head in one vjp).

    both_per_tick=False: one action per stage per tick (used with the
    lax.cond executor — a stage's tick costs only its taken action).
    both_per_tick=True: a stage may run one F AND one B in the same tick
    (used with the cond-free uniform executor, which computes both bodies
    every tick anyway — denser packing halves the tick count).

    Deterministic and purely host-side — runs at trace time; the result is
    baked into the compiled program as constant tables."""
    S, M = n_stages, n_micro
    assert S >= 2, "1F1B needs pp >= 2"
    # per stage state
    f_ready = [dict() for _ in range(S)]   # micro -> tick available
    b_ready = [dict() for _ in range(S)]
    x_slot = [dict() for _ in range(S)]    # micro -> xbuf slot
    dx_slot = [dict() for _ in range(S)]
    x_free = [set() for _ in range(S)]
    dx_free = [set() for _ in range(S)]
    x_hwm = [0] * S                        # slot high-water mark
    dx_hwm = [0] * S
    f_done = [0] * S
    b_done = [0] * S
    for m in range(M):
        f_ready[0][m] = 0                  # stage 0 inputs resident
    rows = []
    t = 0
    while sum(b_done) < S * M or sum(f_done) < (S - 1) * M:
        assert t < 8 * (M + S) + 64, "1F1B schedule failed to converge"
        row = {k: [0] * S for k in
               ("f_on", "f_micro", "f_slot", "b_on", "b_micro", "b_xslot",
                "b_dxslot", "recv_on", "recv_slot", "drecv_on",
                "drecv_slot")}
        acts = []
        for s in range(S):
            bs = [m for m, tk in b_ready[s].items() if tk <= t]
            # 1F1B admission cap: stage s keeps at most S-s microbatches
            # in flight (the warmup depth), so stored activations stay
            # O(pp) — B-priority alone lets warmup overfill downstream
            # buffers (Megatron num_warmup_microbatches semantics)
            fs = [m for m, tk in f_ready[s].items() if tk <= t] \
                if s < S - 1 and f_done[s] - b_done[s] < S - s else []
            did_b = False
            if bs:                         # 1F1B: backward has priority
                m = min(bs)
                row["b_on"][s] = 1
                row["b_micro"][s] = m
                row["b_xslot"][s] = x_slot[s].get(m, 0)
                row["b_dxslot"][s] = dx_slot[s].get(m, 0)
                acts.append(("B", s, m))
                did_b = True
            if fs and (both_per_tick or not did_b):
                m = min(fs)
                row["f_on"][s] = 1
                row["f_micro"][s] = m
                row["f_slot"][s] = x_slot[s].get(m, 0)
                acts.append(("F", s, m))
        # commit effects (arrivals land at t+1)
        for kind, s, m in acts:
            if kind == "F":
                del f_ready[s][m]
                f_done[s] += 1
                if s + 1 < S:
                    # allocate the receiver's x slot now; receiver commits
                    # the permuted activation at the start of t+1
                    free = x_free[s + 1]
                    slot = min(free) if free else x_hwm[s + 1]
                    if free and slot in free:
                        free.discard(slot)
                    else:
                        x_hwm[s + 1] += 1
                    x_slot[s + 1][m] = slot
                    if s + 1 == S - 1:
                        b_ready[S - 1][m] = t + 1   # last stage: B = remat
                    else:
                        f_ready[s + 1][m] = t + 1
            else:
                del b_ready[s][m]
                b_done[s] += 1
                if m in x_slot[s]:
                    x_free[s].add(x_slot[s][m])
                if m in dx_slot[s]:
                    dx_free[s].add(dx_slot[s][m])
                if s > 0:
                    free = dx_free[s - 1]
                    slot = min(free) if free else dx_hwm[s - 1]
                    if free and slot in free:
                        free.discard(slot)
                    else:
                        dx_hwm[s - 1] += 1
                    dx_slot[s - 1][m] = slot
                    b_ready[s - 1][m] = t + 1
        rows.append(row)
        t += 1
    # receive tables: stage s commits at tick t what was sent at t-1
    n_ticks = len(rows)
    for t in range(1, n_ticks):
        prev = rows[t - 1]
        for s in range(S):
            if s > 0 and prev["f_on"][s - 1] and s < S:
                m = prev["f_micro"][s - 1]
                rows[t]["recv_on"][s] = 1
                rows[t]["recv_slot"][s] = x_slot[s].get(m, 0)
            if s < S - 1 and prev["b_on"][s + 1]:
                m = prev["b_micro"][s + 1]
                rows[t]["drecv_on"][s] = 1
                rows[t]["drecv_slot"][s] = dx_slot[s].get(m, 0)
    tab = {k: np.asarray([r[k] for r in rows], np.int32)
           for k in rows[0]}
    return Schedule(n_ticks=n_ticks,
                    n_xslots=max(max(x_hwm), 1),
                    n_dxslots=max(max(dx_hwm), 1), **tab)


# ---------------------------------------------------------------------------
# SPMD executor
# ---------------------------------------------------------------------------

def pipeline_1f1b_grads(stage_fn: Callable, last_fn: Callable,
                        stage_params: Any, shared_params: Any,
                        mb_inputs, mb_ids, mesh, axis_name: str = "pp",
                        aux_weight: float = 0.0, key=None,
                        uniform_last: bool = False,
                        uniform_all: bool = False):
    """Run the 1F1B schedule and return grads directly.

    Args:
      stage_fn: (local_params, x, key) -> (y, aux). One stage's layers.
      last_fn: (local_params, shared_params, x, ids_mb, key)
        -> (y, loss_mb, aux). The final stage: layers + head + loss for
        ONE microbatch — y is the stage output activation (its cotangent
        is seeded by the executor), loss_mb that microbatch's mean loss.
      stage_params: pytree, leaves stacked [S, ...], sharded P(axis, ...).
      shared_params: pytree replicated over the pp axis (head/LN weights).
      mb_inputs: [M, mb, T, H] microbatched, pp-replicated activations.
      mb_ids: [M, mb, T] microbatched token ids (labels for the loss).
      aux_weight: weight of the per-stage aux (MoE load balance) in the
        total loss.
      key: dropout PRNG key or None.
      uniform_last: run blocks+head with cotangent-masked seeds on EVERY
        stage's B-tick instead of lax.cond-ing last vs middle. XLA's SPMD
        partitioner Check-fails on conditionals whose branches carry
        collectives when TWO auto mesh axes (e.g. dp and tp) are active
        beside the manual pp axis; the uniform body avoids the per-stage
        cond at the price of re-running the head on non-final stages'
        B-ticks.
      uniform_all: additionally drop the f_on/b_on scheduling conds —
        EVERY stage runs the F and B bodies on EVERY tick with the
        results where-masked. Required when the stage bodies carry
        EXPLICIT in-body collectives (sp x pp: ring attention's
        ppermutes over "sp" inside the stage functions) — a collective
        inside a stage-divergent lax.cond deadlocks the ring at runtime
        (half the devices enter the rendezvous, half take the other
        branch). Costs bubble-tick compute; correctness-identical.

    Returns (loss, d_stage_params [S,...], d_shared, d_mb_inputs):
      loss = mean over microbatches of loss_mb + aux_weight * sum of aux.
    """
    if uniform_all:
        uniform_last = True   # the cond-free B body is the uniform one
    S = mesh.shape[axis_name]
    M = mb_inputs.shape[0]
    if M < S:
        raise ValueError(f"need microbatches >= stages, got {M} < {S}")
    sched = simulate_1f1b(S, M)
    tabs = {k: jnp.asarray(getattr(sched, k)) for k in
            ("f_on", "f_micro", "f_slot", "b_on", "b_micro", "b_xslot",
             "b_dxslot", "recv_on", "recv_slot", "drecv_on", "drecv_slot")}
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [((i + 1) % S, i) for i in range(S)]
    inv_m = 1.0 / M
    if key is None:
        key = jax.random.PRNGKey(0)

    def spmd(params, shared, mbs, ids):
        stage = jax.lax.axis_index(axis_name)
        local = jax.tree_util.tree_map(lambda x: x[0], params)
        mb_shape = mbs.shape[1:]
        act_dt = mbs.dtype
        zero_act = jnp.zeros(mb_shape, act_dt)

        def stage_key(m):
            return jax.random.fold_in(jax.random.fold_in(key, stage), m)

        def f_mid(l, x, m):
            y, _ = stage_fn(l, x, stage_key(m))
            return y

        carry = dict(
            xbuf=jnp.zeros((sched.n_xslots,) + mb_shape, act_dt),
            dxbuf=jnp.zeros((sched.n_dxslots,) + mb_shape, act_dt),
            y_in=zero_act, dx_in=zero_act,
            gl=jax.tree_util.tree_map(
                lambda v: jnp.zeros(v.shape, jnp.float32), local),
            gsh=jax.tree_util.tree_map(
                lambda v: jnp.zeros(v.shape, jnp.float32), shared),
            dx0=jnp.zeros((M,) + mb_shape, act_dt),
            loss=jnp.zeros((), jnp.float32),
        )

        def tick(carry, t):
            row = {k: v[t] for k, v in tabs.items()}
            my = {k: row[k][stage] for k in row}
            # commit last tick's arrivals into the slot buffers
            xbuf = jnp.where(
                my["recv_on"] > 0,
                jax.lax.dynamic_update_index_in_dim(
                    carry["xbuf"], carry["y_in"], my["recv_slot"], 0),
                carry["xbuf"])
            dxbuf = jnp.where(
                my["drecv_on"] > 0,
                jax.lax.dynamic_update_index_in_dim(
                    carry["dxbuf"], carry["dx_in"], my["drecv_slot"], 0),
                carry["dxbuf"])

            # ---- forward action (never fires on the last stage) -------
            fm = my["f_micro"]
            fx_own = jax.lax.dynamic_index_in_dim(mbs, fm, 0,
                                                  keepdims=False)
            fx_buf = jax.lax.dynamic_index_in_dim(xbuf, my["f_slot"], 0,
                                                  keepdims=False)
            fx = jnp.where(stage == 0, fx_own, fx_buf)
            if uniform_all:
                # cond-free: collectives inside f_mid must execute on
                # every device every tick (see uniform_all docstring)
                y_live = f_mid(local, fx, fm)
                y_out = jnp.where(my["f_on"] > 0, y_live, zero_act)
            else:
                y_out = jax.lax.cond(my["f_on"] > 0,
                                     lambda _: f_mid(local, fx, fm),
                                     lambda _: zero_act, None)

            # ---- backward action --------------------------------------
            # buffer reads/updates and grad accumulation stay OUTSIDE the
            # conds (where-masked): sharded-state updates inside a cond
            # under (dp auto) x (pp manual) x (tp auto) trip the XLA SPMD
            # partitioner's group bookkeeping; only the vjp compute is
            # conditional
            bm = my["b_micro"]
            bx_own = jax.lax.dynamic_index_in_dim(mbs, bm, 0,
                                                  keepdims=False)
            bx_buf = jax.lax.dynamic_index_in_dim(xbuf, my["b_xslot"], 0,
                                                  keepdims=False)
            bx = jnp.where(stage == 0, bx_own, bx_buf)
            bdy = jax.lax.dynamic_index_in_dim(dxbuf, my["b_dxslot"], 0,
                                               keepdims=False)
            bids = jax.lax.dynamic_index_in_dim(ids, bm, 0, keepdims=False)

            def do_b(_):
                if uniform_last:
                    # no per-stage cond: the B body runs blocks+head with
                    # the cotangent seeds masked by stage role
                    def f(l, sh, xx):
                        return last_fn(l, sh, xx, bids, stage_key(bm))
                    (yy, lm, aux), vjp = jax.vjp(f, local, shared, bx)
                    is_last = stage == S - 1
                    dy_eff = jnp.where(is_last, jnp.zeros_like(bdy), bdy)
                    lm_ct = jnp.where(is_last, inv_m,
                                      0.0).astype(lm.dtype)
                    dl, dsh, dx = vjp(
                        (dy_eff, lm_ct,
                         jnp.asarray(aux_weight * inv_m, aux.dtype)))
                    dloss = jnp.where(is_last, lm * inv_m, 0.0) + \
                        aux_weight * inv_m * aux
                    return dl, dsh, dx, dloss.astype(jnp.float32)

                def b_last(_):
                    def f(l, sh, xx):
                        return last_fn(l, sh, xx, bids, stage_key(bm))
                    (yy, lm, aux), vjp = jax.vjp(f, local, shared, bx)
                    dl, dsh, dx = vjp((jnp.zeros_like(yy),
                                       jnp.asarray(inv_m, lm.dtype),
                                       jnp.asarray(aux_weight * inv_m,
                                                   aux.dtype)))
                    return (dl, dsh, dx,
                            (lm * inv_m +
                             aux_weight * inv_m * aux).astype(jnp.float32))

                def b_mid(_):
                    def f(l, xx):
                        return stage_fn(l, xx, stage_key(bm))
                    (yy, aux), vjp = jax.vjp(f, local, bx)
                    dl, dx = vjp((bdy, jnp.asarray(aux_weight * inv_m,
                                                   aux.dtype)))
                    dsh = jax.tree_util.tree_map(jnp.zeros_like, shared)
                    return (dl, dsh, dx,
                            (aux_weight * inv_m * aux).astype(jnp.float32))

                return jax.lax.cond(stage == S - 1, b_last, b_mid, None)

            def no_b(_):
                return (jax.tree_util.tree_map(jnp.zeros_like, local),
                        jax.tree_util.tree_map(jnp.zeros_like, shared),
                        zero_act, jnp.zeros((), jnp.float32))

            if uniform_all:
                dl, dsh, dx_out, dloss = do_b(None)
            else:
                dl, dsh, dx_out, dloss = jax.lax.cond(
                    my["b_on"] > 0, do_b, no_b, None)
            bon = my["b_on"] > 0
            gl = jax.tree_util.tree_map(
                lambda a, b: a + jnp.where(bon, b.astype(jnp.float32), 0),
                carry["gl"], dl)
            gsh = jax.tree_util.tree_map(
                lambda a, b: a + jnp.where(bon, b.astype(jnp.float32), 0),
                carry["gsh"], dsh)
            dx0 = jnp.where(
                jnp.logical_and(bon, stage == 0),
                jax.lax.dynamic_update_index_in_dim(
                    carry["dx0"], dx_out.astype(carry["dx0"].dtype), bm, 0),
                carry["dx0"])
            loss = carry["loss"] + jnp.where(
                bon, dloss.astype(jnp.float32), 0.0)

            # ---- ring communication (uniform across stages) -----------
            y_next = jax.lax.ppermute(y_out, axis_name, fwd_perm)
            dx_next = jax.lax.ppermute(dx_out, axis_name, bwd_perm)
            new_carry = dict(xbuf=xbuf, dxbuf=dxbuf, y_in=y_next,
                             dx_in=dx_next, gl=gl, gsh=gsh, dx0=dx0,
                             loss=loss)
            return new_carry, None

        carry, _ = jax.lax.scan(tick, carry, jnp.arange(sched.n_ticks))
        # no collectives here: per-stage partials come back stacked over
        # the pp axis and are reduced OUTSIDE the manual region (a psum
        # over the manual axis on tp-auto-sharded operands trips XLA's
        # SPMD partitioner group bookkeeping)
        gl = jax.tree_util.tree_map(lambda g: g[None], carry["gl"])
        gsh = jax.tree_util.tree_map(lambda g: g[None], carry["gsh"])
        return carry["loss"][None], gl, gsh, carry["dx0"][None]

    loss, gl, gsh, dx0 = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(P(axis_name), P(), P(), P()),
        out_specs=(P(axis_name), P(axis_name), P(axis_name), P(axis_name)),
        axis_names=frozenset({axis_name}),
        check_vma=False,
    )(stage_params, shared_params, mb_inputs, mb_ids)
    gsh = jax.tree_util.tree_map(lambda g: jnp.sum(g, axis=0), gsh)
    return jnp.sum(loss), gl, gsh, dx0[0]

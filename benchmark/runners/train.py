"""Runner of a trained configuration: builds the program's
`HybridParallelTrainStep` once, drives that one object from the seed
through its first three steps (which the reference follows), then hands
the same object to the timed window.

From the program it takes the trainer class, its `params` / `opt_state`
attributes (the state a user checkpoints) and the loss each call returns.
The weights are the benchmark's own, made on the device from the seed in
one call and put in the trainer's place for its own (the trainer draws its
own on the host first; see PERF.md, Open questions).
"""
from __future__ import annotations

import time

import numpy as np

from . import gpt_config
from ..lib import harness, stats, traffic as traffic_lib

SPAN = "bench.train_step"       # handing a step to the device
WAIT_SPAN = "bench.train_wait"  # waiting for the step before it
COMPARED_STEPS = 3


def _trainer(ctx):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel.hybrid import HybridParallelTrainStep

    tcfg = ctx.config["trainer"]
    hyper = ctx.config["optimizer"]
    gcfg = gpt_config(ctx.config, attn_impl=tcfg["attn_impl"],
                      remat=tcfg["remat"], fused_blocks=tcfg["fused_blocks"])
    pp, tp = int(tcfg["pp"]), int(tcfg["tp"])
    t0 = time.perf_counter()
    step = HybridParallelTrainStep(
        gcfg, pp=pp, tp=tp, dp=int(tcfg.get("dp", 1)),
        n_microbatches=ctx.traffic.get("microbatches"),
        lr=hyper["lr"], weight_decay=hyper["weight_decay"],
        beta1=hyper["beta1"], beta2=hyper["beta2"],
        epsilon=hyper["epsilon"], grad_clip_norm=hyper["grad_clip_norm"],
        seed=ctx.seed % (2**31 - 1))
    t1 = time.perf_counter()
    # what was asked for is what runs (the trainer may switch paths)
    asked = (gcfg.attn_impl, gcfg.fused_blocks)
    got = (step.cfg.attn_impl, step.cfg.fused_blocks)
    if asked != got:
        raise RuntimeError(f"asked for (attention, fused tail) {asked}, "
                           f"the trainer runs {got}")
    step.params = _weights(ctx, step, pp)
    jax.block_until_ready(step.params)
    ctx.say(f"trainer built in {t1 - t0:.1f}s (its own host draw "
            f"included); benchmark weights from seed {ctx.seed} put in "
            f"its place in {time.perf_counter() - t1:.2f}s")
    return step


def _stack(pp):
    """Rearrangement of the reference layout into the trainer's: block
    leaves [L, ...] become [pp, L/pp, ...] under a pipeline."""
    if pp == 1:
        return None

    def reshape(tree):
        tree = dict(tree)
        tree["blocks"] = {k: v.reshape(pp, v.shape[0] // pp, *v.shape[1:])
                          for k, v in tree["blocks"].items()}
        return tree
    return reshape


def _weights(ctx, step, pp):
    import jax
    import jax.numpy as jnp
    shardings = jax.tree_util.tree_map(lambda a: a.sharding, step.params)
    return ctx.reference().make_weights(
        ctx.config["sizes"], ctx.seed, jnp.float32,
        out_shardings=shardings, reshape=_stack(pp))


def _unstack(tree, pp):
    if pp == 1:
        return tree
    tree = dict(tree)
    tree["blocks"] = {k: v.reshape(-1, *v.shape[2:])
                      for k, v in tree["blocks"].items()}
    return tree


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda a: float(jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32))))), tree)


def first_steps(ctx, step, feed):
    """The window's own call on its own feed, three times. Returns what
    the reference is compared with: the losses, the norm of each leaf of
    the first gradient as the optimizer got it (from Adam's first moment
    after one step: m1 = (1 - beta1) g), the norm of each leaf of the
    parameters' change over the three."""
    import jax
    import jax.numpy as jnp
    pp = int(ctx.config["trainer"]["pp"])
    b1 = float(ctx.config["optimizer"]["beta1"])
    losses, batches, grad = [], [], None
    for i in range(COMPARED_STEPS):
        ids = next(feed)
        batches.append(ids)
        with jax.profiler.TraceAnnotation(SPAN):
            losses.append(float(jax.block_until_ready(step(ids))))
        if i == 0:
            m1 = jax.tree_util.tree_map(
                lambda s: s["m1"], step.opt_state,
                is_leaf=lambda s: isinstance(s, dict) and "m1" in s)
            grad = _leaf_norms(_unstack(jax.tree_util.tree_map(
                lambda m: m / (1.0 - b1), m1), pp))
    start = _weights(ctx, step, pp)
    change = _leaf_norms(_unstack(jax.jit(
        lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b))(
            step.params, start), pp))
    del start
    return losses, grad, change, batches


def worst_leaf(got: dict, want: dict) -> tuple[float, str]:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero)."""
    import jax
    g = jax.tree_util.tree_leaves_with_path(got)
    w = jax.tree_util.tree_leaves(want)
    med = float(np.median([float(x) for x in w]))
    worst, where = 0.0, ""
    for (path, a), b in zip(g, w):
        gap = abs(float(a) - float(b)) / max(float(b), med)
        if gap > worst:
            worst, where = gap, jax.tree_util.keystr(path)
    return worst, where


def compare(ctx, got, want):
    """Each number beside its limit. `got`/`want`: (losses, first-gradient
    leaf norms, parameter-change leaf norms)."""
    lim = ctx.config["correct"]
    loss_gap = max(abs(a - b) for a, b in zip(got[0], want[0]))
    ctx.say(f"losses {got[0]} reference {want[0]}")
    ctx.check("widest gap of a step's loss from the reference's", loss_gap,
              float(lim["loss_gap_limit"]))
    g, where = worst_leaf(got[1], want[1])
    ctx.check(f"first gradient's norm, worst leaf ({where})", g,
              float(lim["grad_norm_limit"]))
    c, where = worst_leaf(got[2], want[2])
    ctx.check(f"parameters' change over {COMPARED_STEPS} steps, norm, worst "
              f"leaf ({where})", c, float(lim["change_norm_limit"]))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    tr, sizes = ctx.traffic, ctx.config["sizes"]
    chips = int(ctx.cell["chips"])
    step = _trainer(ctx)
    feed = traffic_lib.train_batches(tr, sizes["vocab_size"], ctx.seed, 0)
    got = first_steps(ctx, step, feed)
    batches = got[3]
    ctx.say(f"first {COMPARED_STEPS} steps: losses {got[0]}; set-up so far "
            f"{ctx.setup_seconds():.2f}s")
    ctx.freeze_gc()
    lowered0 = ctx.lowerings

    # ---- the window -------------------------------------------------------
    t0 = time.perf_counter()
    setup_s = ctx.setup_seconds(t0)
    t_end = t0 + ctx.seconds
    t_trace = t_end - min(harness.TRACE_SECONDS, ctx.seconds)
    tracing, done, losses, pending = False, [], [], None
    # One step is kept in flight, as a training loop that does not read
    # every loss keeps it: step k+1 is handed to the device before the
    # host waits for step k, so the device never waits for the host. A
    # step's time is the distance between two completions.
    while True:
        now = time.perf_counter()
        if now >= t_end and pending is None:
            break
        if ctx.trace and not tracing and now >= t_trace:
            ctx.start_trace()
            tracing = True
        nxt = None
        if now < t_end:
            with jax.profiler.TraceAnnotation(SPAN):
                nxt = step(next(feed))
        if pending is not None:
            with jax.profiler.TraceAnnotation(WAIT_SPAN):
                losses.append(float(jax.block_until_ready(pending)))
            done.append(time.perf_counter())
        pending = nxt
    reduced = ctx.stop_trace() if tracing else None
    window_s = done[-1] - t0
    mem_peak = harness.memory_peak_bytes(jax, chips)
    gate = harness.gate_decisions()
    lowered1 = ctx.lowerings

    tokens = int(tr["batch"]) * int(tr["seq"])
    ends = [t0] + done
    durs = [b - a for a, b in zip(ends, ends[1:])]
    # a traced step is slower: it is left out of the traced run's median
    untraced = [d for a, d in zip(ends, durs)
                if not (tracing and a >= t_trace)] or durs
    p50 = stats.median(untraced)
    # all the steps of the window over all its time (it ends with the step
    # in progress at its nominal end)
    e2e = {"train_tok_s_chip": tokens * len(durs) / window_s / chips,
           "setup_s": setup_s}
    ctx.say(f"window {window_s:.3f}s: {len(durs)} steps, "
            f"{e2e['train_tok_s_chip']:.1f} tok/s/chip; over the median "
            f"step ({p50 * 1e3:.2f} ms) {tokens / p50 / chips:.1f}")
    bad = [x for x in losses if not np.isfinite(x)]
    ctx.check("steps of the window with a loss that is not finite",
              len(bad), 0)
    ctx.check("programs lowered inside the window", lowered1 - lowered0, 0)

    # ---- the reference follows the first three steps ----------------------
    hyper = ctx.config["optimizer"]
    del step, feed
    ctx.release()
    t0r = time.perf_counter()
    ref = ctx.reference()
    params = ref.make_weights(sizes, ctx.seed, jnp.float32,
                              out_shardings=_ref_placement(chips, ref, sizes))
    want = ref.train_steps(params, batches, sizes, hyper,
                           row_block=int(ctx.config["correct"]["row_block"]))
    want = (want[0], *(jax.tree_util.tree_map(float, t) for t in want[1:]))
    ctx.say(f"reference: {COMPARED_STEPS} steps in "
            f"{time.perf_counter() - t0r:.1f}s")
    compare(ctx, got, want)
    if ctx.control:
        ctrl = ref.train_steps(
            params, batches, sizes, hyper, precision=ctx.control,
            row_block=int(ctx.config["correct"]["row_block"]))
        loss_gap = max(abs(a - b) for a, b in zip(ctrl[0], want[0]))
        ctx.control_readings.update(
            loss_gap=loss_gap, grad_norm=worst_leaf(ctrl[1], want[1])[0],
            change_norm=worst_leaf(ctrl[2], want[2])[0])
        ctx.say(f"CONTROL {ctx.control}: loss gap {loss_gap!r}, first "
                f"gradient worst leaf {worst_leaf(ctrl[1], want[1])!r}, "
                f"change worst leaf {worst_leaf(ctrl[2], want[2])!r}")

    return {"end_to_end": e2e, "attempted": len(durs), "failed": len(bad),
            "memory_peak_bytes": mem_peak, "gate": gate, "trace": reduced,
            "step_seconds": untraced, "tokens_per_step": tokens,
            "chips": chips, "config": ctx.config, "traffic": tr,
            "device_kind": jax.devices()[0].device_kind,
            "kind": "train"}


def _ref_placement(chips: int, ref, sizes):
    """One chip holds the reference's state whole; across chips each leaf
    is split over its largest dimension so that the float32 tree and its
    moments fit (plain GSPMD placement, nothing of the program's)."""
    if chips == 1:
        return None
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:chips]), ("x",))

    def place(shape):
        big = max(range(len(shape)), key=lambda i: shape[i])
        if shape[big] % chips:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(*[("x" if i == big else None)
                                       for i in range(len(shape))]))
    return jax.tree_util.tree_map(place, ref.weight_shapes(sizes),
                                  is_leaf=lambda s: isinstance(s, tuple))

#!/usr/bin/env python3
"""PR 27, chip call 3: where do the parent's and the change's sampled
tokens part on the chip? Call 2 found 7 of 60 sampled requests of
decode_closed64 with one token drawn differently (and every later one
with it), no greedy one, and none of 71 in the lfm2 cell. This runs the
two spellings of `sample_tokens` alone, at the cells' shapes, on the same
inputs (logits N(0, 0.9^2) as random weights give them, temperature 0.8,
top-p 0.9), counts the draws that differ, and compares each stage of the
arithmetic bit for bit: the sorted rows, the softmax, the two prefix sums
(as outputs of one program a stage, which no longer fuses as the sampler
does), and both against the change's arithmetic with a barrier behind
every stage (second run of the call; the first had neither).

    python3 benchmark/tools/calls/pr27_sampler_probe.py <parent tree> <change tree> [variants]
"""
import importlib.util
import json
import sys

import numpy as np


def load(tree, name):
    spec = importlib.util.spec_from_file_location(
        name, f"{tree}/paddle_tpu/serving/sampling.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def staged(sort_rows):
    """The sampler's arithmetic behind the sort, every stage an output."""
    import jax
    import jax.numpy as jnp

    def f(logits, temps, topps):
        scaled = logits / jnp.where(temps > 0, temps, 1.0)[:, None]
        sl = sort_rows(scaled)
        probs = jax.nn.softmax(sl, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        keep = (csum - probs) < topps[:, None]
        cdf = jnp.cumsum(jnp.where(keep, probs, 0.0), axis=-1)
        return {"sl": sl, "probs": probs, "csum": csum, "cdf": cdf}
    return f


def by_gather(scaled):
    import jax.numpy as jnp
    order = jnp.argsort(-scaled, axis=-1)
    return jnp.take_along_axis(scaled, order, axis=-1)


def by_sort(scaled):
    import jax
    import jax.numpy as jnp
    iota = jax.lax.broadcasted_iota(jnp.int32, scaled.shape, 1)
    return -jax.lax.sort((-scaled, iota), dimension=1, num_keys=1,
                         is_stable=True)[0]


def barriered(mod):
    """The change's `sample_tokens` with an optimisation barrier behind
    every stage, so that no two stages share a fusion: the arithmetic as
    written, for telling which fused program rounds another way."""
    import jax
    import jax.numpy as jnp
    bar = jax.lax.optimization_barrier

    def f(logits, temps, topks, topps, seeds, steps):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        V = logits.shape[-1]
        scaled = bar(logits / jnp.where(temps > 0, temps, 1.0)[:, None])
        iota = jax.lax.broadcasted_iota(jnp.int32, scaled.shape, 1)
        neg_sl, order = bar(jax.lax.sort((-scaled, iota), dimension=1,
                                         num_keys=1, is_stable=True))
        sl = bar(-neg_sl)
        m = bar(jnp.max(sl, axis=-1, keepdims=True))
        e = bar(jnp.exp(sl - m))
        probs = bar(e / bar(jnp.sum(e, axis=-1, keepdims=True)))
        k_eff = jnp.where(topks > 0, jnp.clip(topks, 1, V), V)
        rank = jnp.arange(V, dtype=jnp.int32)[None, :]
        csum = bar(jnp.cumsum(probs, axis=-1))
        keep = (rank < k_eff[:, None]) & ((csum - probs) < topps[:, None])
        w = bar(jnp.where(keep, probs, 0.0))
        cdf = bar(jnp.cumsum(w, axis=-1))
        u = mod._uniform(jnp, seeds, steps)
        target = bar(u * cdf[:, -1])
        pick = jnp.sum((cdf <= target[:, None]).astype(jnp.int32), axis=-1)
        pick = jnp.clip(pick, 0, V - 1)
        sampled = jnp.take_along_axis(order, pick[:, None],
                                      axis=-1)[:, 0].astype(jnp.int32)
        return jnp.where(temps > 0, sampled, greedy)
    return f


def main(parent_tree, change_tree):
    import jax
    import jax.numpy as jnp
    old = jax.jit(load(parent_tree, "sampling_parent").sample_tokens)
    change = load(change_tree, "sampling_change")
    new, plain = jax.jit(change.sample_tokens), jax.jit(barriered(change))
    print("device", jax.devices()[0].device_kind, flush=True)
    for S, V, steps in ((32, 50304, 400), (64, 65536, 200), (1, 50304, 400),
                        (32, 50176, 400)):
        rng = np.random.default_rng([S, V])
        temps = jnp.asarray(np.where(np.arange(S) % 2 == 0, 0.8, 0.0)
                            if S > 1 else np.asarray([0.8]), jnp.float32)
        topks = jnp.zeros((S,), jnp.int32)
        topps = jnp.full((S,), 0.9, jnp.float32)
        seeds = jnp.asarray(rng.integers(0, 2 ** 32, size=(S, 2),
                                         dtype=np.uint32))
        differ = draws = greedy_differ = old_off = new_off = 0
        stages = {}
        f_old, f_new = jax.jit(staged(by_gather)), jax.jit(staged(by_sort))
        for step in range(steps):
            logits = jnp.asarray(
                0.9 * rng.standard_normal((S, V)).astype(np.float32))
            st = jnp.full((S,), step, jnp.int32)
            a = np.asarray(old(logits, temps, topks, topps, seeds, st))
            b = np.asarray(new(logits, temps, topks, topps, seeds, st))
            c = np.asarray(plain(logits, temps, topks, topps, seeds, st))
            sampled = np.asarray(temps) > 0
            old_off += int(np.sum(a != c))
            new_off += int(np.sum(b != c))
            differ += int(np.sum(a[sampled] != b[sampled]))
            greedy_differ += int(np.sum(a[~sampled] != b[~sampled]))
            draws += int(sampled.sum())
            if step < 20:
                so, sn = f_old(logits, temps, topps), f_new(logits, temps,
                                                            topps)
                for k in so:
                    x = np.asarray(so[k]).view(np.uint32)
                    y = np.asarray(sn[k]).view(np.uint32)
                    d = stages.setdefault(k, {"elements_differ": 0,
                                              "rows_differ": 0})
                    d["elements_differ"] += int(np.sum(x != y))
                    d["rows_differ"] += int(np.sum(np.any(x != y, axis=1)))
        print("PROBE " + json.dumps({
            "shape": [S, V], "steps": steps, "sampled_draws": draws,
            "sampled_differ": differ, "greedy_differ": greedy_differ,
            "parent_differs_from_barriered": old_off,
            "change_differs_from_barriered": new_off,
            "stages_over_20_steps": stages}), flush=True)


def with_barriers(mod, after_sort, after_rows):
    """The change's `sample_tokens` with the sorted rows held behind a
    barrier (after the sort, after the negation, or both): does the
    softmax behind it then round as the parent's, whose rows came
    materialised out of the gather?"""
    import jax
    import jax.numpy as jnp
    real_sort = jax.lax.sort

    def f(*args):
        def sort(operands, **kw):
            out = real_sort(operands, **kw)
            if after_sort:
                out = jax.lax.optimization_barrier(out)
            neg_sl, order = out
            if after_rows:
                # -(-x) behind a barrier: the caller's negation of this
                # gives the rows back, materialised
                neg_sl = -jax.lax.optimization_barrier(-neg_sl)
            return neg_sl, order
        jax.lax.sort = sort
        try:
            return mod.sample_tokens(*args)
        finally:
            jax.lax.sort = real_sort
    return f


def variants(parent_tree, change_tree):
    """Draws of each barrier variant that differ from the parent's fused
    program, logits drawn on the device, many steps."""
    import jax
    import jax.numpy as jnp
    old = jax.jit(load(parent_tree, "sampling_parent").sample_tokens)
    change = load(change_tree, "sampling_change")
    fns = {"change": jax.jit(change.sample_tokens),
           "bar_after_sort": jax.jit(with_barriers(change, True, False)),
           "bar_after_rows": jax.jit(with_barriers(change, False, True)),
           "bar_both": jax.jit(with_barriers(change, True, True))}
    draw = jax.jit(lambda key, shape: 0.9 * jax.random.normal(
        key, shape, jnp.float32), static_argnums=1)
    for S, V, steps in ((32, 50304, 3000), (64, 65536, 600),
                        (1, 50304, 1500), (1, 65536, 1500),
                        (32, 50176, 1000), (4, 50304, 1500)):
        rng = np.random.default_rng([S, V, 2])
        temps = jnp.full((S,), 0.8, jnp.float32)
        topks = jnp.zeros((S,), jnp.int32)
        topps = jnp.full((S,), 0.9, jnp.float32)
        seeds = jnp.asarray(rng.integers(0, 2 ** 32, size=(S, 2),
                                         dtype=np.uint32))
        off = {k: 0 for k in fns}
        for step in range(steps):
            logits = draw(jax.random.fold_in(jax.random.PRNGKey(V + S),
                                             step), (S, V))
            st = jnp.full((S,), step, jnp.int32)
            a = old(logits, temps, topks, topps, seeds, st)
            for k, fn in fns.items():
                off[k] += int(jnp.sum(
                    a != fn(logits, temps, topks, topps, seeds, st)))
        print("VARIANTS " + json.dumps({
            "shape": [S, V], "draws": S * steps,
            "differ_from_parent": off}), flush=True)


if __name__ == "__main__":
    (variants if sys.argv[3:] == ["variants"] else main)(*sys.argv[1:3])

"""Broken timed paths of the routed-expert layer, for setting the limits
of `correct` and for the tests that hold them (benchmark/tests/
test_hybrid_correct.py). Each is a context manager that patches the
PROGRAM (never the reference) while it is open:

  select_on_s      the router chooses on the score alone, not score + bias
  weigh_by_biased  the chosen experts are weighed by score + bias
  no_normalise     the chosen weights are not normalised
  drop_pair        every third token loses its last expert (weight 0):
                   what a capacity-based layer does to an overflowing token
"""
from __future__ import annotations

import contextlib

FAULTS = ("select_on_s", "weigh_by_biased", "no_normalise", "drop_pair")


@contextlib.contextmanager
def fault(name: str):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.parallel import moe

    sound = moe.sigmoid_topk_route

    def broken(h, wg, bias, top_k, norm_topk=True, scale=1.0):
        if name == "select_on_s":
            return sound(h, wg, None, top_k, norm_topk, scale)
        if name == "no_normalise":
            return sound(h, wg, bias, top_k, False, scale)
        sel, g = sound(h, wg, bias, top_k, norm_topk, scale)
        if name == "weigh_by_biased":
            s = jax.nn.sigmoid(jnp.dot(
                h.astype(jnp.float32), wg.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)) + bias
            g = jnp.take_along_axis(s, sel, axis=-1)
            g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6) * scale
        elif name == "drop_pair":
            third = (jnp.arange(g.shape[0]) % 3 == 0)[:, None]
            last = jnp.arange(g.shape[1])[None, :] == g.shape[1] - 1
            g = jnp.where(third & last, 0.0, g)
        else:
            raise ValueError(f"unknown fault {name!r}; has {FAULTS}")
        return sel, g

    moe.sigmoid_topk_route = broken
    try:
        yield
    finally:
        moe.sigmoid_topk_route = sound

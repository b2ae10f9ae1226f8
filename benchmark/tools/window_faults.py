"""Broken timed paths of the windowed decoder (two kinds of attention layer:
the full layers' K/V paged, the sliding layers' in a ring a slot), for setting the limits of `correct` and for the tests
that hold them (benchmark/tests/test_window_correct.py; tests/
test_serving_windowed.py runs the same six against the logits). Each is a
context manager that patches the PROGRAM (never the reference) while it is
open:

  window_whole   the sliding layers attend to everything they can reach:
                 prefill's band is the whole causal triangle, decode walks
                 its ring from position 0 (whatever the ring still holds)
  full_windowed  the full layer attends to the last `sliding_window`
                 positions only, in prefill and in decode
  rope_on_full   the full layer's q and k are rotated like a sliding
                 layer's (it carries no positions)
  no_gate        the attention output goes to the out-projection without
                 its sigmoid gate
  no_shared      the shared expert's term is left out of every expert
                 layer (the routed part stays)
  ring_short     decode reads the ring one page short: a sliding layer's
                 walk starts at the page after the one its first live
                 position lies in
"""
from __future__ import annotations

import contextlib

FAULTS = ("window_whole", "full_windowed", "rope_on_full", "no_gate",
          "no_shared", "ring_short")


@contextlib.contextmanager
def fault(name: str):
    import jax.numpy as jnp
    from paddle_tpu.models import afmoe
    from paddle_tpu.serving import model as serving_model

    # decode's two calls: the sliding layers' (first, ring), the full one's
    ringed = serving_model.paged_attention_xla
    patches = []        # (owner, attribute, broken)
    if name == "window_whole":
        def ring_call(q, ck, cv, table, ctx, first, **kw):
            return ringed(q, ck, cv, table, ctx, first=jnp.zeros_like(first),
                          **kw)
        patches = [(afmoe, "window_of", lambda cfg, l: None),
                   (serving_model, "paged_attention_xla", ring_call)]
    elif name == "full_windowed":
        window = []     # the model's, seen when prefill is traced (first)

        def window_of(cfg, l):
            window[:] = [cfg.sliding_window]
            return cfg.sliding_window

        def full_call(q, ck, cv, table, ctx, impl=None, **kw):
            return ringed(q, ck, cv, table, ctx,
                          first=jnp.maximum(ctx - window[0], 0), **kw)
        patches = [(afmoe, "window_of", window_of),
                   (serving_model, "paged_attention_decode", full_call)]
    elif name == "rope_on_full":
        patches = [(afmoe, "rotates", lambda cfg, l: True)]
    elif name == "no_gate":
        patches = [(afmoe, "output_gate", lambda p, h: jnp.ones(
            h.shape[:-1] + (p["w_gate"].shape[1],), jnp.float32))]
    elif name == "no_shared":
        sound = afmoe.routed_ffn
        patches = [(afmoe, "routed_ffn", lambda p, h, cfg: sound(
            {k: v for k, v in p.items() if k != "shared"}, h, cfg))]
    elif name == "ring_short":
        def ring_call(q, ck, cv, table, ctx, first, **kw):
            ps = ck.shape[-3]
            return ringed(q, ck, cv, table, ctx, first=jnp.minimum(
                (first // ps + 1) * ps, ctx - 1), **kw)
        patches = [(serving_model, "paged_attention_xla", ring_call)]
    else:
        raise ValueError(f"unknown fault {name!r}; has {FAULTS}")
    sound_of = [(o, a, getattr(o, a)) for o, a, _ in patches]
    for o, a, broken in patches:
        setattr(o, a, broken)
    try:
        yield
    finally:
        for o, a, sound in sound_of:
            setattr(o, a, sound)

"""Broken timed paths of the trained routed decoder (models/mellum.py: a
band on three layers of four, YaRN on the fourth, softmax-routed experts
of which a share is held), for setting the limits of `correct` and for the
tests that hold them (benchmark/tests/test_routed_train_correct.py). Each
is a context manager that patches the PROGRAM (never the reference) while
it is open:

  band_short           the sliding layers' band is one position short
                       (i - j < window - 1)
  window_whole         a sliding layer attends to everything before it
  yarn_on_sliding      the sliding layers rotate by YaRN's table and factor
  yarn_off_full        the full layer rotates by the plain table, factor 1
  no_attention_factor  the full layer's cos and sin lose the 1.277 factor
                       (YaRN's frequencies stay)
  sigmoid_router       scores by sigmoid in place of softmax
  no_normalise         the chosen experts' weights are not normalised over
                       the 8 (norm_topk_prob off)
  wrong_experts        the weights held are used as the NEXT share's
                       (experts 16-31's): another part of the layer
  no_qk_norm           q and k go to RoPE without their RMSNorm
"""
from __future__ import annotations

import contextlib

FAULTS = ("band_short", "window_whole", "yarn_on_sliding", "yarn_off_full",
          "no_attention_factor", "sigmoid_router", "no_normalise",
          "wrong_experts", "no_qk_norm")


class _Config:
    """A model configuration with some fields overridden (what
    `layers.routed_ffn` reads of it)."""

    def __init__(self, cfg, **over):
        self._cfg, self._over = cfg, over

    def __getattr__(self, name):
        over = object.__getattribute__(self, "_over")
        if name in over:
            return over[name]
        return getattr(object.__getattribute__(self, "_cfg"), name)


@contextlib.contextmanager
def fault(name: str):
    from paddle_tpu.models import mellum

    attend, table, routed, norm = (mellum.attend, mellum.rope_table,
                                   mellum.routed_ffn, mellum.rmsnorm)

    def routed_as(**over):
        def broken(p, h, cfg):
            if "experts_held" in over:
                n = len(cfg.held)
                over["experts_held"] = tuple(
                    (e + n) % cfg.num_experts for e in cfg.held)
            return routed(p, h, _Config(cfg, **over))
        return broken

    if name == "band_short":
        patches = [("attend", lambda q, k, v, scale, window, impl: attend(
            q, k, v, scale, window and window - 1, impl))]
    elif name == "window_whole":
        patches = [("attend", lambda q, k, v, scale, window, impl: attend(
            q, k, v, scale, None, impl))]
    elif name == "yarn_on_sliding":
        patches = [("rope_table", lambda cfg, kind: table(cfg, mellum.FULL))]
    elif name == "yarn_off_full":
        patches = [("rope_table",
                    lambda cfg, kind: table(cfg, mellum.SLIDING))]
    elif name == "no_attention_factor":
        patches = [("rope_table", lambda cfg, kind: (table(cfg, kind)[0],
                                                     1.0))]
    elif name == "sigmoid_router":
        patches = [("routed_ffn", routed_as(score_func="sigmoid"))]
    elif name == "no_normalise":
        patches = [("routed_ffn", routed_as(norm_topk_prob=False))]
    elif name == "wrong_experts":
        patches = [("routed_ffn", routed_as(experts_held=None))]
    elif name == "no_qk_norm":
        patches = [("rmsnorm", lambda x, w, eps: x if x.ndim == 4
                    else norm(x, w, eps))]
    else:
        raise ValueError(f"unknown fault {name!r}; has {FAULTS}")
    sound = [(a, getattr(mellum, a)) for a, _ in patches]
    for a, broken in patches:
        setattr(mellum, a, broken)
    try:
        yield
    finally:
        for a, was in sound:
            setattr(mellum, a, was)

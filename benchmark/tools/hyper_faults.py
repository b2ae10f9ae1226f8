"""Broken timed paths of the latent-attention decoder on hyper-connected
residual streams, for setting the limits of `correct` and for the tests
that hold them (benchmark/tests/test_hyper_correct.py; tests/
test_serving_hyper.py runs the same five against the logits). Each is a
context manager that patches the PROGRAM (never the reference) while it is
open:

  sinkhorn_1       the carry-over matrix after ONE Sinkhorn iteration in
                   place of 20: its rows sum to 1, its columns do not
  post_unscaled    the branch's output is written back by sigmoid, not by
                   2 sigmoid: every write half as large
  q_norm_dropped   the low-rank query without its norm: wq_b(h wq_a)
  mscale_dropped   decode's absorbed attention scales its scores by
                   1/sqrt(192) alone, without YaRN's mscale^2 (2.0047 at
                   factor 64). Prefill (expanded) stays sound
  streams_mean_in  a branch reads the plain mean of the streams, not the
                   learned, input-dependent mix H_pre X
"""
from __future__ import annotations

import contextlib
import math

FAULTS = ("sinkhorn_1", "post_unscaled", "q_norm_dropped", "mscale_dropped",
          "streams_mean_in")


@contextlib.contextmanager
def fault(name: str):
    from paddle_tpu.models import deepseek_v3 as ds
    from paddle_tpu.serving import model as serving_model

    if name == "sinkhorn_1":
        owner, attr, sound = ds, "hc_coefficients", ds.hc_coefficients

        def broken(p, X, iters, eps, clamp):
            return sound(p, X, 1, eps, clamp)
    elif name == "post_unscaled":
        owner, attr, sound = ds, "hc_coefficients", ds.hc_coefficients

        def broken(*a):
            pre, post, res = sound(*a)
            return pre, 0.5 * post, res
    elif name == "q_norm_dropped":
        owner, attr, sound = ds, "low_rank_query", ds.low_rank_query

        def broken(p, h, eps):
            return (h @ p["wq_a"]) @ p["wq_b"]
    elif name == "mscale_dropped":
        owner, attr = serving_model.LatentDecodeModel, "decode"
        sound = serving_model.LatentDecodeModel.decode

        def broken(self, *a):
            config, scale = type(self.cfg), type(self.cfg).softmax_scale
            config.softmax_scale = property(
                lambda cfg: 1.0 / math.sqrt(cfg.qk_head_dim))
            try:
                return sound(self, *a)
            finally:
                config.softmax_scale = scale
    elif name == "streams_mean_in":
        owner, attr, sound = ds, "hc_read", ds.hc_read

        def broken(X, pre):
            return sum(X) / len(X)
    else:
        raise ValueError(f"unknown fault {name!r}; has {FAULTS}")
    setattr(owner, attr, broken)
    try:
        yield
    finally:
        setattr(owner, attr, sound)

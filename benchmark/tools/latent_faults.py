"""Broken timed paths of the latent-attention decoder, for setting the
limits of `correct` and for the tests that hold them (benchmark/tests/
test_latent_correct.py; tests/test_serving_latent.py runs the same three
against the logits). Each is a context manager that patches the PROGRAM
(never the reference) while it is open:

  no_shared      the shared experts' term is left out of every expert
                 layer (the routed part stays): a sixth of an expert
                 layer's active weights not read
  scale_576      decode's absorbed attention scales its scores by
                 1/sqrt(576), the width of the absorbed query, instead of
                 1/sqrt(192), the width of the product it stands for.
                 Prefill (expanded) stays sound
  kr_unrotated   the shared rope key kr goes into the rows, and into
                 prefill's own attention, WITHOUT its rotation (q_rope
                 keeps its own): position leaves the keys
"""
from __future__ import annotations

import contextlib
import math

FAULTS = ("no_shared", "scale_576", "kr_unrotated")


@contextlib.contextmanager
def fault(name: str):
    from paddle_tpu.models import deepseek_v3 as ds
    from paddle_tpu.serving import model as serving_model

    if name == "no_shared":
        owner, attr, sound = ds, "routed_ffn", ds.routed_ffn

        def broken(p, h, cfg):
            return sound({k: v for k, v in p.items() if k != "shared"}, h,
                         cfg)
    elif name == "scale_576":
        owner, attr = serving_model, "paged_latent_attention_decode"
        sound = serving_model.paged_latent_attention_decode

        def broken(q, *a, scale, **kw):
            return sound(q, *a, scale=1.0 / math.sqrt(q.shape[-1]), **kw)
    elif name == "kr_unrotated":
        owner, attr, sound = ds, "latent_projections", ds.latent_projections

        def broken(p, h, positions, cfg):
            q_nope, q_rope, c, _kr = sound(p, h, positions, cfg)
            return q_nope, q_rope, c, \
                (h @ p["wkv_a"])[..., cfg.kv_lora_rank:]
    else:
        raise ValueError(f"unknown fault {name!r}; has {FAULTS}")
    setattr(owner, attr, broken)
    try:
        yield
    finally:
        setattr(owner, attr, sound)

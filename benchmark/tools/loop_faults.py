"""Broken timed paths of the looped decoder, for setting the limit of
`correct` and for the tests that hold it (benchmark/tests/
test_looped_correct.py). Each is a context manager that patches the
PROGRAM (never the reference) while it is open:

  three_passes    the program is built with total_ut_steps - 1 passes (the
                  reference keeps the configuration's): a quarter of the
                  mathematics left out, and a quarter faster for it
  attend_pass_0   every pass's decode attends over pass 0's K/V rows
                  (row l and not t L + l; the writes go where they
                  belong): what a cache shared between the passes reads.
                  Prefill is dense within each pass and stays sound
"""
from __future__ import annotations

import contextlib
import dataclasses

FAULTS = ("three_passes", "attend_pass_0")


@contextlib.contextmanager
def fault(name: str):
    from benchmark.runners import serve_looped
    from paddle_tpu.serving import model as serving_model
    Looped = serving_model.LoopedDecodeModel

    if name == "three_passes":
        sound = serve_looped.model_config

        def fewer(config):
            cfg = sound(config)
            return dataclasses.replace(
                cfg, total_ut_steps=cfg.total_ut_steps - 1)
        serve_looped.model_config = fewer
        try:
            yield
        finally:
            serve_looped.model_config = sound
    elif name == "attend_pass_0":
        sound, attn = Looped.decode, serving_model.paged_attention_decode

        def decode(self, *a):
            L = self.cfg.num_hidden_layers

            def pass_0(q, k, v, tables, ctx, layer=None, **kw):
                return attn(q, k, v, tables, ctx, layer=layer % L, **kw)
            serving_model.paged_attention_decode = pass_0
            try:
                return sound(self, *a)
            finally:
                serving_model.paged_attention_decode = attn
        Looped.decode = decode
        try:
            yield
        finally:
            Looped.decode = sound
    else:
        raise ValueError(f"unknown fault {name!r}; has {FAULTS}")

"""Broken timed paths of the recurrent decoder (Mamba layers' state per
slot beside paged multi-query attention), for setting the limits of
`correct` and for the tests that hold them (benchmark/tests/
test_recurrent_correct.py). Each is a context manager that patches the
PROGRAM (never the reference) while it is open:

  state_bf16            the cache keeps the recurrence's state in bfloat16
                        (the model's dtype): a layer reads it up to
                        float32 and rounds what it hands back, once a
                        prefill, every decode step
  padding_advances      the prefill scan takes no lengths: the bucket's
                        padding advances the recurrence past `true_len`
  stale_state           a prefill starts from the rows its slot holds (the
                        last tenant's state and taps), not from zeros
  taps_from_bucket_end  a prefill keeps the convolution's inputs at the
                        bucket's last K-1 positions, not those before
                        `true_len`
  no_dt_norm, no_b_norm, no_c_norm
                        one of the mixer's three inner norms is left out
  no_d_skip             y = h C without D c
"""
from __future__ import annotations

import contextlib

FAULTS = ("state_bf16", "padding_advances", "stale_state",
          "taps_from_bucket_end", "no_dt_norm", "no_b_norm", "no_c_norm",
          "no_d_skip")


@contextlib.contextmanager
def fault(name: str):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import jamba
    from paddle_tpu.serving.model import RecurrentDecodeModel

    mixer, scan, step = jamba.mamba_mixer, jamba.selective_scan, \
        jamba.selective_step
    patches = []        # (owner, attribute, broken)
    if name == "state_bf16":
        zero = jamba.zero_state

        def rounded(p, a, ssm, conv, lengths, cfg):
            out, ssm, conv = mixer(p, a, ssm.astype(jnp.float32), conv,
                                   lengths, cfg)
            return out, ssm.astype(jnp.bfloat16), conv

        def zero_bf16(cfg, batch, dtype):
            ssm, conv = zero(cfg, batch, dtype)
            return ssm.astype(jnp.bfloat16), conv
        patches = [(jamba, "mamba_mixer", rounded),
                   (jamba, "zero_state", zero_bf16)]
    elif name == "padding_advances":
        def unmasked(u, delta, A, B, C, D, h0=None, lengths=None, **kw):
            return scan(u, delta, A, B, C, D, h0, None, **kw)
        patches = [(jamba, "selective_scan", unmasked)]
    elif name == "stale_state":
        sound, zero = RecurrentDecodeModel.prefill, jamba.zero_state

        def prefill(self, params, cache, tokens, true_len, page_row, slot):
            rows = jax.lax.dynamic_slice_in_dim
            stale = (rows(cache["ssm"], slot, 1, axis=1),
                     rows(cache["conv"], slot, 1, axis=2))
            jamba.zero_state = lambda cfg, batch, dtype: stale
            try:
                return sound(self, params, cache, tokens, true_len, page_row,
                             slot)
            finally:
                jamba.zero_state = zero
        patches = [(RecurrentDecodeModel, "prefill", prefill)]
    elif name == "taps_from_bucket_end":
        def at_the_end(p, a, ssm, conv, lengths, cfg):
            out, ssm, kept = mixer(p, a, ssm, conv, lengths, cfg)
            if lengths is not None:
                u = (a @ p["w_in"])[..., :cfg.d_inner]
                kept = jnp.swapaxes(u[:, 1 - cfg.mamba_d_conv:], 0, 1)
            return out, ssm, kept.astype(a.dtype)
        patches = [(jamba, "mamba_mixer", at_the_end)]
    elif name in ("no_dt_norm", "no_b_norm", "no_c_norm"):
        norm, skipped = jamba.rmsnorm, []

        def one_left_out(p, a, ssm, conv, lengths, cfg):
            skipped[:] = [p[name[3:]]]
            return mixer(p, a, ssm, conv, lengths, cfg)

        def rmsnorm(x, w, eps):
            return x if skipped and w is skipped[0] else norm(x, w, eps)
        patches = [(jamba, "mamba_mixer", one_left_out),
                   (jamba, "rmsnorm", rmsnorm)]
    elif name == "no_d_skip":
        def scan_no_d(u, delta, A, B, C, D, *a, **kw):
            return scan(u, delta, A, B, C, jnp.zeros_like(D), *a, **kw)

        def step_no_d(u, delta, A, B, C, D, h):
            return step(u, delta, A, B, C, jnp.zeros_like(D), h)
        patches = [(jamba, "selective_scan", scan_no_d),
                   (jamba, "selective_step", step_no_d)]
    else:
        raise ValueError(f"unknown fault {name!r}; has {FAULTS}")
    kept = [(o, attr, getattr(o, attr)) for o, attr, _ in patches]
    for o, attr, broken in patches:
        setattr(o, attr, broken)
    try:
        yield
    finally:
        for o, attr, sound_fn in kept:
            setattr(o, attr, sound_fn)

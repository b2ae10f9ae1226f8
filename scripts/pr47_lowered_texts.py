#!/usr/bin/env python3
"""What PR 47 must leave byte for byte, as sha256 of lowered text: the
expert layer of the three serving configurations that run it (every expert
held: `experts_held=None`), forward in both spellings and the gradient of
the sorted one, and the GPT training step. Run from the root of each tree
(it imports that tree's `paddle_tpu`) and `diff` the two outputs:

    JAX_PLATFORMS=cpu python3 scripts/pr47_lowered_texts.py
"""
import hashlib
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_include_full_tracebacks_in_locations", False)

# (D, experts, F, k): the expert layers of lfm2_8b_a1b_serve,
# kanana2_30b_a3b_serve and trinity_mini_serve, each at a decode batch's
# and a prefill bucket's rows
LAYERS = {"lfm2": (2048, 32, 1792, 4), "kanana": (2048, 128, 768, 6),
          "trinity": (2048, 128, 1024, 8)}


def sha(text: str) -> str:
    text = re.sub(r"loc\([^)]*\)", "", text).replace(os.getcwd(), "")
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def main():
    from paddle_tpu.parallel import moe
    for name, (D, E, F, k) in LAYERS.items():
        for N in (64, 2048):
            s = lambda *sh, dt=jnp.bfloat16: jax.ShapeDtypeStruct(sh, dt)
            args = (s(N, D), s(D, E, dt=jnp.float32), s(E, dt=jnp.float32),
                    s(E, D, F), s(E, D, F), s(E, F, D))
            for impl in ("gmm", "dense"):
                f = lambda h, wg, b, w1, w3, w2: moe.dropless_moe_ffn(
                    h, wg, b, w1, w3, w2, top_k=k, impl=impl)
                print(sha(jax.jit(f).lower(*args).as_text()),
                      f"{name} N={N} {impl} forward")
            g = jax.grad(lambda h, wg, b, w1, w3, w2: jnp.sum(
                moe.dropless_moe_ffn(h, wg, b, w1, w3, w2, top_k=k,
                                     impl="gmm")[0].astype(jnp.float32)),
                         (0, 1, 3, 4, 5))
            print(sha(jax.jit(g).lower(*args).as_text()),
                  f"{name} N={N} gmm gradient")

    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.parallel.hybrid import HybridParallelTrainStep
    for kw in ({}, {"attn_impl": "flash", "amp_dtype": "bfloat16"}):
        cfg = GPTConfig.tiny(**kw)
        step = HybridParallelTrainStep(cfg, seed=0,
                                       devices=jax.devices()[:1])
        ids = jnp.asarray(np.zeros((2, 128), np.int32))
        text = step._jit_step.lower(
            step.params, step.opt_state, step._pows, ids, np.float32(1e-4),
            jax.random.PRNGKey(0)).as_text()
        print(sha(text), f"GPT tiny {kw} training step")


if __name__ == "__main__":
    main()

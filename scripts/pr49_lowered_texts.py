#!/usr/bin/env python3
"""What PR 49 must leave byte for byte, as sha256 of lowered text: the
serving programs (`prefill`, `decode`) of the three models whose core
imports what the PR touched (`models/layers.py`; `models/deepseek_v3.py`
with `hc_mult`, `q_lora_rank` and `rope_scaling` all None): kanana's
`LatentDecodeModel` at its tiny and its published shapes (7 layers, 64
slots, buckets 1,024 and 8,192), lfm2's and trinity's adapters at tiny
shapes. Lowered from shapes, for the CPU (the XLA attention path) and with
the Pallas kernels. Run from the root of each tree (it imports that tree's
`paddle_tpu`) and `diff` the two outputs:

    JAX_PLATFORMS=cpu python3 scripts/pr49_lowered_texts.py
"""
import functools
import hashlib
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_include_full_tracebacks_in_locations", False)


def sha(text: str) -> str:
    text = re.sub(r"loc\([^)]*\)", "", text).replace(os.getcwd(), "")
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def programs(model, params, slots, pages, page_size, max_pages, buckets):
    """(name, lowered text) of the model's prefill at each bucket and of
    its decode."""
    cache = jax.eval_shape(functools.partial(
        model.init_cache, pages, page_size, slots))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    for T in buckets:
        yield f"prefill {T}", jax.jit(model.prefill).lower(
            params, cache, i32(T), i32(), i32(max_pages), i32()).as_text()
    yield f"decode {slots}", jax.jit(model.decode).lower(
        params, cache, i32(slots), i32(slots),
        i32(slots, max_pages)).as_text()


def main():
    from paddle_tpu.models import afmoe, deepseek_v3 as ds, lfm2
    from paddle_tpu.serving import (HybridDecodeModel, LatentDecodeModel,
                                    WindowedDecodeModel)
    kanana = ds.DeepseekV3Config(num_hidden_layers=7, dtype="bfloat16")
    cases = [
        ("kanana tiny", LatentDecodeModel, ds, ds.DeepseekV3Config.tiny(),
         (4, 64, 8, 16, (16, 64))),
        ("kanana published", LatentDecodeModel, ds, kanana,
         (64, 6400, 64, 160, (1024, 8192))),
        ("lfm2 tiny", HybridDecodeModel, lfm2, lfm2.LFM2Config.tiny(),
         (4, 64, 8, 16, (16, 64))),
        ("trinity tiny", WindowedDecodeModel, afmoe,
         afmoe.AfmoeConfig.tiny(), (4, 64, 8, 16, (16, 64))),
    ]
    for name, cls, core, cfg, shape in cases:
        params = jax.eval_shape(lambda: core.init_params(cfg, 0))
        for impl in ("xla", "pallas"):
            model = cls(cfg, params={}, attn_impl=impl)
            for prog, text in programs(model, params, *shape):
                print(sha(text), len(text.splitlines()), name, impl, prog)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Compile the windowed model's serving programs at Trinity-Mini's widths
(6 layers) for a DESCRIBED TPU v5e (no chip attached; on-chip-measurement
guide, section 2), as scripts/pr32_compile_for_v5e.py does for the latent
model: what the chip's compiler refuses, whether the donated pools (the
full layer's pages, the sliding layers' rings) stay one buffer each, and
what each program keeps as
temporaries (the 16,384 bucket's banded attention above all), at no chip
time. Nothing runs: no time, no result. Run from the repo's root with
JAX_PLATFORMS=cpu.

    python3 scripts/pr40_compile_for_v5e.py [--impl pallas|xla] [--moe gmm|dense]
"""
import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pages", type=int, default=7680)
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--impl", default="pallas")
    ap.add_argument("--moe", default="gmm")
    ap.add_argument("--buckets", default="256,2048,16384")
    ap.add_argument("--text", default="")
    args = ap.parse_args()

    from paddle_tpu.models import afmoe as ds
    from paddle_tpu.ops import paged_attention, pallas_attention
    from paddle_tpu.parallel import moe
    from paddle_tpu.serving import WindowedDecodeModel
    from paddle_tpu.serving.sampling import sample_tokens
    # the kernels for the chip, not the interpreter: this script only
    paged_attention.on_tpu = lambda: True
    pallas_attention.on_tpu = lambda: True
    pallas_attention._interpret = lambda: False
    moe._auto_grouped = lambda h, local, w1: args.moe

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = ds.AfmoeConfig(
        dtype="bfloat16", num_hidden_layers=args.layers,
        layer_types=ds.AfmoeConfig().layer_types[:args.layers])
    dt = jnp.bfloat16

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    shapes = {"embed": (cfg.vocab_size, cfg.hidden_size),
              "head": (cfg.hidden_size, cfg.vocab_size),
              "norm": (cfg.hidden_size,),
              "layers": [ds.layer_shapes(cfg, l)
                         for l in range(cfg.num_hidden_layers)]}
    params = jax.tree_util.tree_map(
        lambda s: spec(s, dt), shapes, is_leaf=lambda s: isinstance(s, tuple))
    model = WindowedDecodeModel.__new__(WindowedDecodeModel)
    model.cfg, model.attn_impl = cfg, args.impl
    model.window = cfg.sliding_window
    S, ps, M = args.slots, 64, 288
    cache = jax.eval_shape(lambda: model.init_cache(args.pages, ps, S))
    cache = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), cache)
    pool = sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(cache))
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree_util.tree_leaves(params))
    print(f"weights {weights / 2**30:.3f} GiB, cache {pool / 2**30:.3f} GiB")
    i32 = lambda *s: spec(s, jnp.int32)     # noqa: E731
    f32 = lambda *s: spec(s, jnp.float32)   # noqa: E731

    def decode(params, cache, tokens, positions, tables, *samp):
        cache, logits = model.decode(params, cache, tokens, positions,
                                     tables)
        return cache, sample_tokens(logits, *samp)

    def prefill(params, cache, tokens, true_len, page_row, slot, *samp):
        cache, logits = model.prefill(params, cache, tokens, true_len,
                                      page_row, slot)
        return cache, sample_tokens(logits[None, :], *samp)[0]

    def report(name, fn, *targs):
        t0 = time.perf_counter()
        try:
            compiled = jax.jit(fn, donate_argnums=(1,)).lower(
                params, cache, *targs).compile()
        except Exception as e:
            print(f"{name}: REFUSED {str(e)[:800]}", flush=True)
            return None
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"{name}: compiled in {time.perf_counter() - t0:.1f}s; "
              f"arguments {m.argument_size_in_bytes / 2**30:.3f} GiB, "
              f"outputs {m.output_size_in_bytes / 2**30:.3f}, aliased "
              f"{m.alias_size_in_bytes / 2**30:.3f}, temporaries "
              f"{m.temp_size_in_bytes / 2**30:.3f}; all together "
              f"{total / 2**30:.3f} GiB", flush=True)
        if args.text:
            os.makedirs(args.text, exist_ok=True)
            with open(os.path.join(args.text, name.split("[")[0] + "_"
                                   + "".join(c for c in name if c.isdigit())
                                   + ".hlo.txt"), "w") as f:
                f.write(compiled.as_text())
        return compiled

    samp = lambda n: (f32(n), i32(n), f32(n), i32(n), i32(n))  # noqa: E731
    c = report(f"decode[slots={S},pages={M}]", decode, i32(S), i32(S),
               i32(S, M), *samp(S))
    if c is not None:
        print("  tpu_custom_call in the program:",
              c.as_text().count("tpu_custom_call"))
    for T in map(int, args.buckets.split(",")):
        report(f"prefill[{T}]", prefill, i32(T), i32(), i32(M), i32(),
               *samp(1))


if __name__ == "__main__":
    main()

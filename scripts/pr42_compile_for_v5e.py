#!/usr/bin/env python3
"""Compile the recurrent model's serving programs at AI21-Jamba2-3B's sizes
(all 28 layers, 256 slots) for a DESCRIBED TPU v5e (no chip attached;
on-chip-measurement guide, section 2), as scripts/pr40_compile_for_v5e.py
does for the windowed model: what the chip's compiler refuses (the scan
kernel inside the layer loop above all), whether the donated parts (2.2 GiB
of per-slot state, the K | V pages) stay one buffer each through the loops
over the stacked layers, and what each program keeps as temporaries, at no
chip time. Nothing runs: no time, no result. Run from the repo's root with
JAX_PLATFORMS=cpu.

    python3 scripts/pr42_compile_for_v5e.py [--attn pallas|xla] [--scan pallas|xla]
        [--step pallas|xla] [--buckets 64,2048] [--text <dir>]
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
    ap.add_argument("--pages", type=int, default=6144)
    ap.add_argument("--slots", type=int, default=256)
    ap.add_argument("--attn", default="pallas")
    ap.add_argument("--scan", default="pallas")
    ap.add_argument("--step", default="pallas")
    ap.add_argument("--buckets", default="64,2048")
    ap.add_argument("--text", default="")
    args = ap.parse_args()

    from paddle_tpu.models import jamba
    from paddle_tpu.ops import paged_attention, selective_scan
    from paddle_tpu.serving import RecurrentDecodeModel
    from paddle_tpu.serving.sampling import sample_tokens
    # the kernels for the chip, not the interpreter: this script only
    paged_attention.on_tpu = lambda: True
    selective_scan.on_tpu = lambda: True
    selective_scan._auto_impl = lambda *a: args.scan
    selective_scan._auto_step_impl = lambda *a: args.step

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = jamba.JambaConfig(dtype="bfloat16")
    dt = jnp.bfloat16

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        lambda s: spec(s, dt), jamba.weight_shapes(cfg),
        is_leaf=lambda s: isinstance(s, tuple))
    model = RecurrentDecodeModel.__new__(RecurrentDecodeModel)
    model.cfg, model.attn_impl = cfg, args.attn
    S, ps, M = args.slots, 64, 64
    cache = jax.eval_shape(lambda: model.init_cache(args.pages, ps, S))
    cache = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), cache)
    size = lambda t: sum(a.size * a.dtype.itemsize      # noqa: E731
                         for a in jax.tree_util.tree_leaves(t))
    print(f"weights {size(params) / 2**30:.3f} GiB, cache "
          f"{size(cache) / 2**30:.3f} GiB "
          + str({k: round(size(v) / 2**30, 3) for k, v in cache.items()}))
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
            print(f"{name}: REFUSED {str(e)[:1200]}", flush=True)
            return None
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        text = compiled.as_text()
        print(f"{name}: compiled in {time.perf_counter() - t0:.1f}s; "
              f"arguments {m.argument_size_in_bytes / 2**30:.3f} GiB, "
              f"outputs {m.output_size_in_bytes / 2**30:.3f}, aliased "
              f"{m.alias_size_in_bytes / 2**30:.3f}, temporaries "
              f"{m.temp_size_in_bytes / 2**30:.3f}; all together "
              f"{total / 2**30:.3f} GiB; tpu_custom_call "
              f"{text.count('tpu_custom_call')}", flush=True)
        if args.text:
            os.makedirs(args.text, exist_ok=True)
            with open(os.path.join(args.text, name.split("[")[0] + "_"
                                   + "".join(c for c in name if c.isdigit())
                                   + ".hlo.txt"), "w") as f:
                f.write(text)
        return compiled

    samp = lambda n: (f32(n), i32(n), f32(n), i32(n, 2), i32(n))  # noqa: E731
    report(f"decode[slots={S},pages={M}]", decode, i32(S), i32(S),
           i32(S, M), *samp(S))
    for T in map(int, args.buckets.split(",")):
        report(f"prefill[{T}]", prefill, i32(T), i32(), i32(M), i32(),
               *samp(1))


if __name__ == "__main__":
    main()

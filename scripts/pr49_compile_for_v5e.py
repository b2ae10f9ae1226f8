#!/usr/bin/env python3
"""Compile the latent model's serving programs at the Xing4.0 cell's sizes
(6 layers at the published widths, four residual streams, 32 slots) for a
DESCRIBED TPU v5e (no chip attached; on-chip-measurement guide, section 2),
as scripts/pr42_compile_for_v5e.py does for the recurrent model: what the
chip's compiler refuses, and what each program keeps as temporaries beside
9.59 GB of weights and the pool (a bucket of 16,384 positions carries
several 117 MB streams, the expanded keys and values and the experts'
sorted rows), at no chip time. Nothing runs: no time, no result. Run from
the repo's root with JAX_PLATFORMS=cpu.

    python3 scripts/pr49_compile_for_v5e.py [--pages 5120] [--layers 6]
        [--buckets 2048,16384] [--moe gmm|dense] [--text <dir>]
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pages", type=int, default=0)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--attn", default="pallas")
    ap.add_argument("--moe", default="gmm")
    ap.add_argument("--buckets", default="2048,16384")
    ap.add_argument("--text", default="")
    args = ap.parse_args()

    from benchmark.runners.serve_mla_hyper import model_config
    from paddle_tpu.models import deepseek_v3 as ds
    from paddle_tpu.ops import autobench, paged_attention, pallas_attention
    from paddle_tpu.serving import LatentDecodeModel
    from paddle_tpu.serving.sampling import sample_tokens
    # the kernels for the chip, not the interpreter: this script only
    paged_attention.on_tpu = lambda: True
    pallas_attention.on_tpu = lambda: True
    pallas_attention._interpret = lambda: False
    autobench.prefer = lambda key, cands, make_args, default=None: \
        args.moe if key[0] == "moe_grouped_swiglu" else default

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4_29b_a4b_serve.json")) as f:
        config = json.load(f)
    if args.layers:
        config["num_hidden_layers"] = args.layers
    cfg = model_config(config)
    ecfg = config["engine"]
    pages = args.pages or ecfg["num_pages"]
    S, ps = ecfg["num_slots"], ecfg["page_size"]
    M = ecfg["max_seq_len"] // ps

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: ds.init_params(cfg, 0)))
    model = LatentDecodeModel.__new__(LatentDecodeModel)
    model.cfg, model.attn_impl = cfg, args.attn
    cache = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init_cache(pages, ps, S)))
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
            print(f"{name}: REFUSED {str(e)[:1500]}", flush=True)
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

#!/usr/bin/env python3
"""The Xing4.0 cell's largest prefill bucket (16,384 positions) with the
streams' feed-forward sub-layer in two row blocks of 8,192 (the program's:
`models/deepseek_v3.py::_in_row_blocks`) and in ONE block (the whole
bucket's temporaries at once), beside the cell's weights and its pool of
5,120 pages: the program's milliseconds and the device's peak memory, each
way, from one process on the chip (blocked first: the peak only rises).
The prefill is the engine's (`LatentDecodeModel.prefill` and the sampler
under one jit, the cache donated); weights are the program's own draw and
the prompt random ids, the same both ways.

    python3 scripts/pr49_bucket_both_ways.py [--bucket 16384] [--repeats 5]
    JAX_PLATFORMS=cpu python3 scripts/pr49_bucket_both_ways.py --rehearsal \
        --bucket 64                 # here: that it runs, at the tiny sizes
"""
import argparse
import json
import os
import statistics
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bucket", type=int, default=16384)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--rehearsal", action="store_true",
                    help="the configuration's tiny sizes, for a CPU: "
                         "no time of it means anything")
    args = ap.parse_args()

    from benchmark.runners.serve_mla_hyper import model_config
    from paddle_tpu.models import deepseek_v3 as ds
    from paddle_tpu.serving import LatentDecodeModel
    from paddle_tpu.serving.sampling import sample_tokens

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4_29b_a4b_serve.json")) as f:
        config = json.load(f)
    if args.rehearsal:
        from benchmark.lib.harness import _merge
        _merge(config, config["rehearsal"])
    cfg = model_config(config)
    ecfg = config["engine"]
    S, ps, pages = ecfg["num_slots"], ecfg["page_size"], ecfg["num_pages"]
    M, T = ecfg["max_seq_len"] // ps, args.bucket
    dev = jax.devices()[0]
    print(f"device {dev.device_kind}; bucket {T}, {pages} pages of {ps}, "
          f"{cfg.num_hidden_layers} layers", flush=True)

    params = ds.init_params(cfg, 0)
    model = LatentDecodeModel(cfg, params=params)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, T), jnp.int32)
    row = np.zeros(M, np.int32)
    row[:T // ps] = 1 + np.arange(T // ps)
    samp = (jnp.ones(1), jnp.zeros(1, jnp.int32), jnp.ones(1),
            jnp.zeros((1, 2), jnp.int32), jnp.zeros(1, jnp.int32))

    def prefill(params, cache, tokens, true_len, page_row, slot, *samp):
        cache, logits = model.prefill(params, cache, tokens, true_len,
                                      page_row, slot)
        return cache, sample_tokens(logits[None, :], *samp)[0]

    for block in (T // 2 if args.rehearsal else ds._FFN_ROW_BLOCK, T):
        ds._FFN_ROW_BLOCK = block
        cache = model.init_cache(pages, ps, S)
        fn = jax.jit(lambda *a: prefill(*a), donate_argnums=(1,))
        call = lambda cache: fn(params, cache, tokens,        # noqa: E731
                                jnp.int32(T - 7), jnp.asarray(row),
                                jnp.int32(0), *samp)
        t0 = time.perf_counter()
        try:
            cache, tok = call(cache)
            jax.block_until_ready(tok)
        except Exception as e:          # the chip's memory, most likely
            print(f"blocks of {block}: FAILED {str(e)[:600]}", flush=True)
            continue
        first = time.perf_counter() - t0
        ms = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            cache, tok = call(cache)
            jax.block_until_ready(tok)
            ms.append(1e3 * (time.perf_counter() - t0))
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        print(f"blocks of {block} ({-(-T // block)} a bucket): median "
              f"{statistics.median(ms):.2f} ms of {args.repeats} "
              f"({min(ms):.2f}-{max(ms):.2f}; host clock round one call), "
              f"first call {first:.1f} s, peak so far "
              f"{peak / 2**30:.3f} GiB", flush=True)
        del cache


if __name__ == "__main__":
    main()

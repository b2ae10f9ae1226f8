"""The serving programs of GPTDecodeModel as lowered text, for comparing two
trees (PR 28: a refactor of serving/model.py must leave them byte for byte).

Run it from the root of each tree; it imports that tree's `paddle_tpu`:

  # sandbox: the three bodies, for the TPU platform, kernels not interpreted
  JAX_PLATFORMS=cpu python3 scripts/lowered_serving_programs.py \
      --impl xla --impl pallas --out /root/scratch/lowered/<tree>
  # chip: the engine's own jitted programs, the kernel gate deciding
  python3 scripts/lowered_serving_programs.py --engine --out chiprun_out/...

Everything is lowered at the `gpt_1p3b_serve` shapes from
`jax.ShapeDtypeStruct`s (no weight is made; `--engine` does allocate the
engine's cache, so it wants the chip or `--tiny`). Each program's text,
without `loc(...)`, goes to `<out>/<name>.mlir` and one line
`<sha256> <lines> <name>` to stdout and `<out>/SHA256`: `diff` two of those.

A Pallas kernel's body travels inside its `tpu_custom_call` as serialized
MLIR that keeps its own locations, by default the Python call stack of the
`pallas_call` (file paths, function names, lines: another directory or a
moved line is another body, and another compile-cache key). So this script
turns `jax_include_full_tracebacks_in_locations` off and strips the tree's
root from file names: a body then names the kernel's own lines in
`ops/paged_attention.py` and nothing of its callers.
"""
from __future__ import annotations

import argparse
import hashlib
import os
import re
import sys

sys.path.insert(0, os.getcwd())

BUCKETS = (64, 256, 1024, 2048)
SERVE = dict(vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16,
             max_position_embeddings=2048, intermediate_size=8192,
             amp_dtype="bfloat16")
ENGINE = dict(num_slots=32, num_pages=3072, page_size=16, max_seq_len=2048)
TINY = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
            max_position_embeddings=128, intermediate_size=256,
            amp_dtype="bfloat16")
TINY_ENGINE = dict(num_slots=4, num_pages=64, page_size=8, max_seq_len=128)
TINY_BUCKETS = (16, 64)


def param_structs(cfg, dtype):
    """models.gpt.init_gpt_params' tree as shapes, all in `dtype` (as the
    benchmark's weights are)."""
    import jax
    D, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    shapes = {
        "wte": (cfg.vocab_size, D),
        "wpe": (cfg.max_position_embeddings, D),
        "blocks": {
            **{n: (L, D) for n in ("ln1_s", "ln1_b", "bq", "bk", "bv", "bo",
                                   "ln2_s", "ln2_b", "b_down")},
            **{n: (L, D, D) for n in ("wq", "wk", "wv", "wo")},
            "w_up": (L, D, F), "b_up": (L, F), "w_down": (L, F, D)},
        "lnf_s": (D,), "lnf_b": (D,)}
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, dtype), shapes,
        is_leaf=lambda s: isinstance(s, tuple))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--impl", action="append",
                    choices=("xla", "pallas", "auto"))
    ap.add_argument("--engine", action="store_true",
                    help="lower Engine._prefill/_prefill_tail/_decode")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.ops import paged_attention
    from paddle_tpu.serving import Engine, GPTDecodeModel

    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(os.getcwd() + os.sep))
    platform = jax.devices()[0].platform
    impls = args.impl or ["auto"]
    if platform != "tpu":
        if "auto" in impls:
            ap.error("--impl auto asks the kernel gate, which measures: it "
                     "needs the chip")
        # kernels lower to tpu_custom_call, not to their interpreter
        paged_attention.on_tpu = lambda: True
    cfg = GPTConfig(**(TINY if args.tiny else SERVE))
    ecfg = TINY_ENGINE if args.tiny else ENGINE
    buckets = TINY_BUCKETS if args.tiny else BUCKETS
    S, ps = ecfg["num_slots"], ecfg["page_size"]
    M = ecfg["max_seq_len"] // ps
    dt = jnp.dtype(cfg.amp_dtype)
    params = param_structs(cfg, dt)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    os.makedirs(args.out, exist_ok=True)
    lines = []

    def emit(name, fn, *targs):
        jitted = fn if hasattr(fn, "trace") else jax.jit(fn)
        # as_text() leaves source locations out unless asked for them
        text = jitted.trace(*targs).lower(
            lowering_platforms=("tpu",)).as_text()
        with open(os.path.join(args.out, name + ".mlir"), "w") as f:
            f.write(text)
        lines.append(f"{hashlib.sha256(text.encode()).hexdigest()} "
                     f"{text.count(chr(10))} {name}")
        print(lines[-1], flush=True)

    for impl in impls:
        model = GPTDecodeModel(cfg, params={},
                               attn_impl=None if impl == "auto" else impl)
        if args.engine:
            eng = Engine(model, **ecfg)
            cache = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), eng.cache)
            samp = lambda n: (  # noqa: E731
                f32(n), i32(n), f32(n),
                jax.ShapeDtypeStruct((n, 2), jnp.uint32), i32(n))
            for T in buckets:
                emit(f"engine.{impl}.prefill_{T}", eng._prefill, params,
                     cache, i32(T), i32(), i32(M), i32(), i32(S), *samp(1))
                emit(f"engine.{impl}.prefill_tail_{T}", eng._prefill_tail,
                     params, cache, i32(T), i32(), i32(), i32(M), i32(),
                     i32(S), *samp(1))
            emit(f"engine.{impl}.decode", eng._decode, params, cache,
                 i32(S), i32(S), i32(S), i32(S, M), *samp(S))
            del eng
            continue
        cache = jax.eval_shape(
            lambda: model.init_cache(ecfg["num_pages"], ps, S))
        for T in buckets:
            emit(f"{impl}.prefill_{T}", model.prefill, params, cache,
                 i32(T), i32(), i32(M), i32())
            emit(f"{impl}.prefill_tail_{T}", model.prefill_tail, params,
                 cache, i32(T), i32(), i32(), i32(M))
        emit(f"{impl}.decode", model.decode, params, cache, i32(S), i32(S),
             i32(S, M))

    with open(os.path.join(args.out, "SHA256"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"device {platform}; {len(lines)} programs under {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

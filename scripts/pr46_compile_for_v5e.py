#!/usr/bin/env python3
"""Compile the Mellum training step at the cell's sizes (4 layers of
Mellum2-12B-A2.5B, 16 of 64 experts held, 2 x 8,192 tokens) for a DESCRIBED
TPU v5e (no chip attached; on-chip-measurement guide, section 2), as
scripts/pr42_compile_for_v5e.py does for a serving model: what the chip's
compiler refuses (the flash kernel's banded and grouped calls at 8,192
positions, megablox's products at `parallel/moe.py`'s tiles, forward and
backward), whether the step fits the chip, and what it keeps as
temporaries beside 9.5 GB of state, at no chip time. Nothing runs: no time,
no result. The program is the trainer's own (`HybridParallelTrainStep.
_build`), on a mesh of the one described device. Run from the repo's root
with JAX_PLATFORMS=cpu.

    python3 scripts/pr46_compile_for_v5e.py [--moe gmm|dense] [--layers 4]
        [--seq 8192] [--text <file>]
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

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--moe", default="gmm")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--text", default="")
    ap.add_argument("--reference", action="store_true",
                    help="the float32 reference's loss and gradient "
                         "(benchmark/reference/mellum_window_moe.py) in "
                         "place of the program's step")
    args = ap.parse_args()

    from benchmark.runners.train_routed import model_config
    from paddle_tpu.models import mellum
    from paddle_tpu.ops import autobench, pallas_attention
    from paddle_tpu.parallel import hybrid
    # the kernels for the chip, not the interpreter: this script only
    pallas_attention.on_tpu = lambda: True
    pallas_attention._interpret = lambda: False
    autobench.prefer = lambda key, cands, make_args, default=None: \
        args.moe if key[0] == "moe_grouped_swiglu" else default

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2_12b_a2p5b_train.json")) as f:
        config = json.load(f)
    config["num_hidden_layers"] = args.layers
    config["layer_types"] = (config["layer_types"] * 7)[:args.layers]
    config["mlp_layer_types"] = ["sparse"] * args.layers
    cfg = model_config(config, attn_impl="flash", remat=True)
    model = mellum.MellumTrainModel(cfg)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1, 1, 1, 1),
                ("pp", "dp", "sp", "ep", "tp"))
    repl = NamedSharding(mesh, P())
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=repl)
    shapes = mellum.param_shapes(cfg)
    is_shape = lambda s: isinstance(s, tuple)
    params = jax.tree_util.tree_map(lambda s: spec(s, jnp.float32), shapes,
                                    is_leaf=is_shape)
    n = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        shapes, is_leaf=is_shape))
    print(f"{n:,} parameters x 16 B = {n * 16 / 1e9:.2f} GB")

    gib = lambda b: f"{b / 2**30:.2f} GiB"
    if args.reference:
        from benchmark.reference import mellum_window_moe as ref
        from benchmark.runners.train_routed import sizes_of
        sizes = sizes_of(config)
        L, k = cfg.num_hidden_layers, cfg.num_experts_per_tok
        t0 = time.perf_counter()
        compiled = jax.jit(jax.value_and_grad(
            lambda p, ids, rt: ref.batch_loss(
                p, ids, sizes, "f32", rt,
                int(config["correct"]["row_block"])), has_aux=True)).lower(
            params, spec((args.batch, args.seq), jnp.int32),
            spec((L, args.batch * args.seq, k), jnp.int32)).compile()
        m = compiled.memory_analysis()
        print(f"reference loss and gradient compiled in "
              f"{time.perf_counter() - t0:.1f}s: arguments "
              f"{gib(m.argument_size_in_bytes)}, outputs "
              f"{gib(m.output_size_in_bytes)}, temporaries "
              f"{gib(m.temp_size_in_bytes)}")
        if args.text:
            with open(args.text, "w") as f:
                f.write(compiled.as_text())
        return

    t = hybrid.HybridParallelTrainStep.__new__(hybrid.HybridParallelTrainStep)
    t.model, t.mesh, t.pp, t._schedule = model, mesh, 1, "gpipe"
    t._hyper = dict(beta1=0.9, beta2=0.999, epsilon=1e-8)
    t._wd, t._clip = 0.01, 1.0
    t._decays = [path[-1].key in model.decay for path, _s in
                 jax.tree_util.tree_flatten_with_path(params)[0]]
    t._shardings = jax.tree_util.tree_map(lambda _s: repl, params)
    t._opt_shardings = jax.tree_util.tree_map(
        lambda _s: {"m1": repl, "m2": repl}, params)
    t._tally = {"chosen": spec((cfg.num_hidden_layers, cfg.num_experts),
                               jnp.int32),
                "over_bound": spec((cfg.num_hidden_layers,), jnp.int32)}
    step = t._build(mesh)
    opt = jax.tree_util.tree_map(lambda p: {"m1": p, "m2": p}, params)
    pows = (spec((1,), jnp.float32), spec((1,), jnp.float32))
    t0 = time.perf_counter()
    lowered = step.lower(params, opt, pows, t._tally,
                         spec((args.batch, args.seq), jnp.int32),
                         np.float32(1e-4), jax.random.PRNGKey(0))
    compiled = lowered.compile()
    print(f"compiled in {time.perf_counter() - t0:.1f}s")
    m = compiled.memory_analysis()
    print(f"arguments {gib(m.argument_size_in_bytes)}, outputs "
          f"{gib(m.output_size_in_bytes)}, aliased "
          f"{gib(m.alias_size_in_bytes)}, temporaries "
          f"{gib(m.temp_size_in_bytes)}: peak about "
          f"{gib(m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes)}")
    text = compiled.as_text()
    print("tpu_custom_call kernels:", text.count('custom_call_target="tpu_custom_call"'))
    if args.text:
        with open(args.text, "w") as f:
            f.write(text)


if __name__ == "__main__":
    main()

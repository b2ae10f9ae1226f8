#!/usr/bin/env python3
"""How far the share of token-expert pairs that fall on the experts held
wanders between seeds, and the step's time with it: the trained routed
cell's program alone (no reference, no window), a few steps a seed, at
one or several learning rates (0 keeps the seeded weights' routing). On
the chip:

    python3 benchmark/tools/routed_train_held.py --lrs 1e-4,1e-6,0 \
        --seeds 4 --steps 10 [--workload <cell>]

Prints one line a (rate, seed): the held share over the steps, by layer,
the busiest held expert over the mean, and the median step. Never part of
the benchmark's own runs.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import harness, stats, traffic as traffic_lib  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload",
                    default="mellum2_12b_a2p5b_train.b2s8192")
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--lrs", default="",
                    help="learning rates to try (default: the file's)")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        for part in ("config", "traffic"):
            harness._merge(cell[part], cell[part].get("rehearsal", {}))
    else:
        harness.place_caches()
    import jax
    from benchmark.runners import train_routed

    lrs = [float(x) for x in args.lrs.split(",") if x] \
        or [cell["config"]["optimizer"]["lr"]]
    for lr in lrs:
        cell["config"]["optimizer"]["lr"] = lr
        for i in range(args.seeds):
            seed = 2147560000 + 104729 * i
            ns = argparse.Namespace(seed=seed, seconds=1, trace=0)
            ctx = harness.Context(cell, ns, time.perf_counter(),
                                  args.rehearsal)
            ctx.config["sizes"] = train_routed.sizes_of(ctx.config)
            ctx.say = lambda *a: None
            step = train_routed._trainer(ctx)
            feed = traffic_lib.train_batches(
                ctx.traffic, ctx.config["sizes"]["vocab_size"], seed, 0)
            times, loss = [], None
            for _ in range(args.steps):
                t0 = time.perf_counter()
                loss = float(jax.block_until_ready(step(next(feed))))
                times.append(time.perf_counter() - t0)
            t = step.tally_stats()
            by_layer = [round(100.0 * sum(row) * len(t["held_counts"])
                              / t["pairs_routed"], 2)
                        for row in t["held_counts"]]
            top = [round(max(row) * len(row) / max(sum(row), 1), 2)
                   for row in t["held_counts"]]
            print(f"lr {lr:g} seed {seed}: held "
                  f"{100.0 * t['pairs_held'] / t['pairs_routed']:.3f}% by "
                  f"layer {by_layer} busiest/mean {top} step p50 "
                  f"{1e3 * stats.median(times[2:] or times):.2f} ms loss "
                  f"{loss:.4f}", flush=True)
            ctx.trainer = None
            del step
    return 0


if __name__ == "__main__":
    sys.exit(main())

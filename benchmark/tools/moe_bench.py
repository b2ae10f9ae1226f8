#!/usr/bin/env python3
"""Times the spellings of the grouped expert products (parallel/moe.py) on
this machine's chip at one layer's shapes, for each row count a serving
engine uses. A tool for deciding what the gate is offered; never part of
the benchmark's runs.

    python3 benchmark/tools/moe_bench.py [--rows 64,128,...]
"""
import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="64,128,256,512,1024,2048")
    ap.add_argument("--sizes", default="32,2048,1792,4")   # E, D, F, k
    args = ap.parse_args(argv)
    import jax
    from paddle_tpu.parallel import moe
    E, D, F, k = map(int, args.sizes.split(","))
    print("device", jax.devices()[0].device_kind, "E,D,F,k", E, D, F, k)
    cands = dict(moe._GROUPED)
    weights = 3 * E * D * F * 2
    for n in map(int, args.rows.split(",")):
        _key, _c, make_args = moe._gate_grouped(n, k, E, D, F, "bfloat16")
        a = make_args()
        row = []
        for name, fn in cands.items():
            try:
                f = jax.jit(fn)
                jax.block_until_ready(f(*a))
                ts = []
                for _ in range(7):
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(*a))
                    ts.append(time.perf_counter() - t0)
                ms = 1e3 * statistics.median(ts)
                row.append(f"{name} {ms:.3f} ms ({weights / ms / 1e6:.0f} "
                           f"GB/s of weights)")
            except Exception as e:       # a spelling the compiler refuses
                row.append(f"{name} FAILED {type(e).__name__}: "
                           f"{str(e)[:120]}")
        print(f"rows {n}: " + "; ".join(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

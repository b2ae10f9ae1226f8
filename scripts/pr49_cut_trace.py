#!/usr/bin/env python3
"""Cut a trace the harness kept (`BENCH_KEEP_TRACE`) to the SHORTEST
prefill program that is followed by two decode programs, and those two,
under one `bench.step` span made here to cover exactly the three, as
scripts/pr42_cut_trace.py does for the recurrent cell: what a test can
hold (benchmark/tests/data/xing_prefill_two_steps.json.gz). Names are cut
to `name length` characters, times moved to start at 0; beside the trace
it writes what the hyper readers' patterns (benchmark/layer_metrics/
hyper_*.json) make of the cut at the cell's sizes, for the test to hold.

    python3 scripts/pr49_cut_trace.py trace.json[.gz] out.json.gz [name length]
"""
import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(src, dst, name_len=900):
    name_len = int(name_len)
    with (gzip.open if src.endswith(".gz") else open)(src, "rt") as f:
        planes = json.load(f)["planes"]
    dev = next(p for p in planes if p["name"].startswith("/device:TPU:"))
    lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
    mods = sorted(lines["XLA Modules"], key=lambda e: e[1])
    kind = lambda m, k: m[0].startswith("jit_" + k)      # noqa: E731
    at = [i for i in range(len(mods) - 2) if kind(mods[i], "prefill")
          and kind(mods[i + 1], "decode") and kind(mods[i + 2], "decode")]
    i = min(at, key=lambda i: mods[i][2])
    lo, hi = mods[i][1], mods[i + 2][1] + mods[i + 2][2]
    keep = lambda evs: [[n[:name_len], s - lo, d] for n, s, d in evs  # noqa: E731
                        if lo <= s and s + d <= hi]
    trace = {"planes": [
        {"name": dev["name"], "lines": [
            {"name": "XLA Ops", "events": keep(lines["XLA Ops"])},
            {"name": "XLA Modules", "events": keep(mods)}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.step", 0, hi - lo]]}]}]}

    from benchmark.lib.trace import Reduced
    from benchmark.readers import hyper
    from benchmark.runners.serve_mla_hyper import sizes_of
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4_29b_a4b_serve.json")) as f:
        cfg = json.load(f)
    cfg["sizes"] = sizes_of(cfg)
    run = {"config": cfg, "trace": Reduced(trace),
           "device_kind": "TPU v5 lite"}
    expect = {"prefill_ms": mods[i][2] / 1e6,
              "decode_ms": [mods[i + 1][2] / 1e6, mods[i + 2][2] / 1e6],
              "operations": len(trace["planes"][0]["lines"][0]["events"])}
    for name in ("hyper_prefill_device_share", "hyper_decode_device_share"):
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            expect[name] = hyper.program_op_share(run,
                                                  **json.load(f)["args"])
    print(expect)
    with gzip.open(dst, "wt") as f:
        json.dump({"trace": trace, "expect": expect,
                   "recorded": "PR 49, chip call 2, the traced run of seed "
                               "2147490011 (TPU v5 lite): the shortest "
                               "prefill program followed by two decode "
                               "programs, cut by scripts/pr49_cut_trace.py"},
                  f)


if __name__ == "__main__":
    main(*sys.argv[1:])

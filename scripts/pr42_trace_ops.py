#!/usr/bin/env python3
"""The operations of a trace the harness kept (`BENCH_KEEP_TRACE`: the raw
planes of benchmark/lib/trace.py), by program: for `jit_decode` and
`jit_prefill`, how many programs the trace holds, their median length, and
their operations by name with their own time (a `while` beside its body's
operations), the largest first. What the recurrent readers' patterns
(benchmark/layer_metrics/ssm_*.json) were written from.

    python3 scripts/pr42_trace_ops.py trace.json[.gz] [rows]
"""
import bisect
import collections
import gzip
import json
import os
import re
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.lib.trace import self_times  # noqa: E402


def main(src, rows=45):
    with (gzip.open if src.endswith(".gz") else open)(src, "rt") as f:
        planes = json.load(f)
    planes = planes.get("trace", planes)["planes"]
    dev = next(p for p in planes if p["name"].startswith("/device:TPU:"))
    lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
    ops = sorted(lines["XLA Ops"], key=lambda e: e[1])
    starts = [e[1] for e in ops]
    for program in ("jit_decode", "jit_prefill"):
        own, durs = collections.Counter(), []
        for name, start, dur in lines["XLA Modules"]:
            if not name.startswith(program):
                continue
            inside = [e for e in ops[bisect.bisect_left(starts, start):
                                     bisect.bisect_right(starts, start + dur)]
                      if e[1] + e[2] <= start + dur]
            durs.append(dur)
            for n, ns in self_times(inside):
                own[re.sub(r"^%?([a-zA-Z_-]+)[.\d]* = ", r"\1 = ", n[:300],
                           count=1)] += ns
        if not durs:
            continue
        total = sum(own.values())
        print(f"{program}: {len(durs)} programs, median "
              f"{statistics.median(durs) / 1e6:.3f} ms, operations' own "
              f"time {total / 1e9:.4f} s")
        for n, ns in own.most_common(int(rows)):
            print(f"  {ns / 1e9:8.4f} s {100 * ns / total:5.1f}%  {n}")


if __name__ == "__main__":
    main(*sys.argv[1:])

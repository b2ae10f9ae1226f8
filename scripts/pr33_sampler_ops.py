#!/usr/bin/env python3
"""The sampler's device time in a trace the harness kept
(`BENCH_KEEP_TRACE`: the raw planes of benchmark/lib/trace.py), beside what
`sampler_device_share`'s pattern takes. In every serving program the
sampler is the tail: nothing of a vocabulary row a slot exists before the
head's product makes the logits. Of each `jit_decode` program this takes
the stretch from the first operation that names an array `[S,V]` (the
head's product, or the fusion it is part of) to the program's end, and
prints, medians over the programs: the program, the stretch, the stretch
less its first operation when that one reads the head's weights (the
sampler alone), and the stretch's operations by name with their own time
(a `while` beside its body's operations).

    python3 scripts/pr33_sampler_ops.py trace.json 64 128256 [program]
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


def main(src, S, V, program="jit_decode"):
    with (gzip.open if src.endswith(".gz") else open)(src, "rt") as f:
        planes = json.load(f)
    planes = planes.get("trace", planes)["planes"]
    dev = next(p for p in planes if p["name"].startswith("/device:TPU:"))
    lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
    wide = re.compile(r"\[%s,%s\]" % (S, V))
    head = re.compile(r"params__(head|embed)__")
    ops = sorted(lines["XLA Ops"], key=lambda e: e[1])
    starts = [e[1] for e in ops]
    rows, own_by_name, count = [], collections.Counter(), 0
    for name, start, dur in lines["XLA Modules"]:
        if not name.startswith(program):
            continue
        inside = [e for e in ops[bisect.bisect_left(starts, start):
                                 bisect.bisect_right(starts, start + dur)]
                  if e[1] + e[2] <= start + dur]
        first = next((i for i, e in enumerate(inside) if wide.search(e[0])),
                     None)
        if first is None:
            continue
        tail = inside[first:]
        end = max(s + d for _n, s, d in inside)
        stretch = end - tail[0][1]
        makes_logits = tail[0][2] if head.search(tail[0][0]) else 0
        gauge = sum(ns for n, ns in self_times(inside)
                    if wide.search(n) and "params__embed__" not in n)
        rows.append((dur, stretch, stretch - makes_logits, gauge,
                     sum(1 for e in tail if " sort(" in e[0])))
        count += 1
        for n, ns in self_times(tail):
            own_by_name[re.sub(r"[.\d]+ = ", " = ", n[:200], count=1)] += ns
    if not rows:
        sys.exit(f"no {program} program with an operation over [{S},{V}]")
    med = lambda i: statistics.median(r[i] for r in rows) / 1e6  # noqa: E731
    print(json.dumps({
        "programs": count, "program_ms": round(med(0), 3),
        "from_the_logits_to_the_end_ms": round(med(1), 3),
        "sampler_alone_ms": round(med(2), 3),
        "the_gauges_pattern_ms": round(med(3), 3),
        "sorts_a_program": rows[0][4]}))
    for name, ns in own_by_name.most_common(14):
        print(f"  {ns / count / 1e6:8.4f} ms a program  {name[:170]}")


if __name__ == "__main__":
    main(*sys.argv[1:5])

#!/usr/bin/env python3
"""What the windowed model's prefill programs are made of, by bucket, from a
trace the harness kept (`BENCH_KEEP_TRACE`: the raw planes of
benchmark/lib/trace.py). A `jit_prefill` program's bucket is read off its
operations (the residual `bf16[1,T,2048]`); each operation's own time goes
to the first of these that its name fits:

  pools       names a K/V pool (`[5,2113,64,4,128]`, `[1,7681,64,4,128]`):
              the page and ring writes and the copies round them
  attention   the flash kernel's calls (`flash_band_fwd`, `flash_full_fwd`)
              or XLA's blocks of scores (`[.., 4, 8, rows, keys]`) and what
              feeds them (`[.., rows, 4, 8, 128]`, a block's slices of K, V)
  experts     the grouped products, the router, the shared expert, the rows'
              sort and gathers: whatever names an expert layer's `ffn` leaf
              or an array of T x 8 pairs
  dense_ffn   the two dense layers' feed-forward
  head        names the vocabulary (200,192)
  projections names an attention layer's weights (q, k, v, gate, o), the
              q/k norms and RoPE (`[T,32,128]`, `[T,4,128]`)
  rest        the four norms a layer, the residual adds, the embedding

    python3 scripts/pr50_prefill_ops.py <dir>/trace.json[.gz] [rows]

Prints, a bucket: programs, median milliseconds, and each part's median
milliseconds a program; then the largest bucket's operations by own time.
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

PARTS = ("pools", "attention", "experts", "dense_ffn", "head",
         "projections", "rest")


def part_of(name: str, T: int) -> str:
    if re.search(r"(2113|7681),64,4,128\]", name):
        return "pools"
    if "flash_" in name or re.search(r"[\[,]4,8,\d+,\d+\]", name) \
            or re.search(r"\d+,4,8,128\]", name) \
            or re.search(r"while|\[1,(2560|2304),4,128\]", name):
        return "attention"
    if "200192" in name:
        return "head"
    if re.search(r"ffn____(wg|bias|shared)|\[128,(2048|1024),(1024|2048)\]"
                 r"|gmm|\[%d[,\]]" % (8 * T), name) \
            or re.search(r"[\[,]%d,8\]|[\[,]%d,128\]" % (T, T), name):
        return "experts"
    if "ffn____w" in name or "6144" in name:
        return "dense_ffn"
    if "attn____" in name \
            or re.search(r"\[(1,)?%d,(32|4)(,128)?\]" % T, name) \
            or re.search(r"\[(1,)?%d,(4096|512)\]" % T, name):
        return "projections"
    return "rest"


def bucket_of(names) -> int:
    seen = collections.Counter()
    for n in names:
        for t in re.findall(r"bf16\[1,(\d+),2048\]", n):
            seen[int(t)] += 1
    return seen.most_common(1)[0][0] if seen else 0


def main(src, rows=40):
    with (gzip.open if src.endswith(".gz") else open)(src, "rt") as f:
        planes = json.load(f)
    planes = planes.get("trace", planes)["planes"]
    dev = next(p for p in planes if p["name"].startswith("/device:TPU:"))
    lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
    ops = sorted(lines["XLA Ops"], key=lambda e: e[1])
    starts = [e[1] for e in ops]
    by_bucket = collections.defaultdict(list)   # T -> [(dur, parts, own)]
    for name, start, dur in lines["XLA Modules"]:
        if not name.startswith("jit_prefill"):
            continue
        inside = [e for e in ops[bisect.bisect_left(starts, start):
                                 bisect.bisect_right(starts, start + dur)]
                  if e[1] + e[2] <= start + dur]
        T = bucket_of(e[0] for e in inside)
        parts, own = collections.Counter(), collections.Counter()
        for n, ns in self_times(inside):
            parts[part_of(n, T)] += ns
            own[re.sub(r"^%?([a-zA-Z_-]+)[.\d]* = ", r"\1 = ", n[:260],
                       count=1)] += ns
        by_bucket[T].append((dur, parts, own))
    for T in sorted(by_bucket):
        progs = by_bucket[T]
        mid = statistics.median(p[0] for p in progs) / 1e6
        split = {k: statistics.median(p[1][k] for p in progs) / 1e6
                 for k in PARTS}
        print(f"prefill[{T}]: {len(progs)} programs, median {mid:.2f} ms "
              f"({1e3 * mid / max(T, 1):.2f} ms per 1,000 positions); "
              + ", ".join(f"{k} {v:.2f}" for k, v in split.items()))
    if by_bucket:
        T = max(by_bucket)
        own = sum((p[2] for p in by_bucket[T]), collections.Counter())
        total = sum(own.values())
        print(f"prefill[{T}], operations by own time over its "
              f"{len(by_bucket[T])} programs:")
        for n, ns in own.most_common(int(rows)):
            print(f"  {ns / 1e6:9.3f} ms {100 * ns / total:5.1f}% "
                  f"[{part_of(n, T)}] {n}")


if __name__ == "__main__":
    main(*sys.argv[1:])

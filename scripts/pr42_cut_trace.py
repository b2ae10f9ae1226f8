#!/usr/bin/env python3
"""Cut a trace the harness kept (`BENCH_KEEP_TRACE`: the raw planes of
benchmark/lib/trace.py) to ONE prefill program whose scan is the Pallas
kernel (a `selective_scan` custom call) and the two decode programs after
it, under ONE `bench.step` span made here to cover exactly the three (the
harness's own steps round a prefill hold other programs too): what a test
can hold (benchmark/tests/data/jamba_prefill_two_steps.json.gz). As
scripts/pr32_cut_trace.py, whose cut holds decode programs alone: the
recurrent readers also read the PREFILL programs (`ssm_scan_roofline`).
Names are cut to `name length` characters (the paged call's pool is its
fifth operand), times moved to start at 0. Prints the cut's operations by
name, largest first.

    python3 scripts/pr42_cut_trace.py trace.json[.gz] out.json.gz [name length]
"""
import collections
import gzip
import json
import re
import sys


def main(src, dst, name_len=420):
    name_len = int(name_len)
    with (gzip.open if src.endswith(".gz") else open)(src, "rt") as f:
        planes = json.load(f)
    planes = planes.get("trace", planes)["planes"]
    dev = next(p for p in planes if p["name"].startswith("/device:TPU:"))
    lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
    mods = sorted(lines["XLA Modules"], key=lambda e: e[1])
    ops = sorted(lines["XLA Ops"], key=lambda e: e[1])
    kind = lambda m, k: m[0].startswith("jit_" + k)      # noqa: E731

    def has_kernel(m):
        return any(n.startswith(("%selective_scan", "selective_scan"))
                   for n, s, d in ops if m[1] <= s and s + d <= m[1] + m[2])
    i = next(i for i in range(len(mods) // 3, len(mods) - 2)
             if kind(mods[i], "prefill") and kind(mods[i + 1], "decode")
             and kind(mods[i + 2], "decode") and has_kernel(mods[i]))
    first, last = mods[i], mods[i + 2]
    lo, hi = first[1] - 1000, last[1] + last[2] + 1000
    keep = lambda evs: [[n[:name_len], s - lo, d] for n, s, d in evs  # noqa: E731
                        if lo <= s and s + d <= hi]
    out = {"planes": [
        {"name": dev["name"], "lines": [
            {"name": "XLA Modules", "events": keep(mods)},
            {"name": "XLA Ops", "events": keep(ops)}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [["bench.step", 0, hi - lo]]}]}]}
    with gzip.open(dst, "wt") as f:
        json.dump({"trace": out}, f, separators=(",", ":"))
    kept = out["planes"][0]["lines"][1]["events"]
    acc, cnt = collections.Counter(), collections.Counter()
    for n, _s, d in kept:
        key = re.sub(r"[.\d]+ = ", " = ", n[:name_len], count=1)
        acc[key] += d
        cnt[key] += 1
    print(f"modules {i}..{i + 2} of {len(mods)}: "
          f"{[(m[0][:20], m[2]) for m in out['planes'][0]['lines'][0]['events']]}"
          f", {len(kept)} operation events in {(hi - lo) / 1e6:.2f} ms")
    for key, ns in acc.most_common(25):
        print(f"  {ns / 1e6:8.3f} ms  x{cnt[key]:<5d} {key[:160]}")


if __name__ == "__main__":
    main(*sys.argv[1:])

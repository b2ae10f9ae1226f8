#!/usr/bin/env python3
"""Cut a trace the harness kept (`BENCH_KEEP_TRACE`: the raw planes of
benchmark/lib/trace.py, hundreds of MB for 5 s of a 192-layer decode) to
two consecutive `bench.step` spans that hold a decode program each and no
prefill, names cut to 200 characters, times moved to start at 0: what a
test can hold (benchmark/tests/data/). Also prints, by name, the device
time of every operation of those two steps, largest first.

    python3 scripts/pr30_cut_trace.py trace.json two_steps.json
"""
import collections
import json
import re
import sys

NAME = 200


def main(src, dst):
    with open(src) as f:
        planes = json.load(f)["planes"]
    dev = next(p for p in planes if p["name"].startswith("/device:TPU:"))
    host = next(p for p in planes if p["name"] == "/host:CPU")
    lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
    mods = sorted(lines["XLA Modules"], key=lambda e: e[1])
    steps = sorted((e for ln in host["lines"] for e in ln["events"]
                    if e[0] == "bench.step"), key=lambda e: e[1])

    def programs(step):
        return [m[0].split("(")[0] for m in mods
                if step[1] <= m[1] and m[1] + m[2] <= step[1] + step[2]]
    pick = next(i for i in range(len(steps) // 2, len(steps) - 1)
                if programs(steps[i]) == ["jit_decode"]
                and programs(steps[i + 1]) == ["jit_decode"])
    a, b = steps[pick], steps[pick + 1]
    lo, hi = a[1], b[1] + b[2]
    keep = lambda evs: [[n[:NAME], s - lo, d] for n, s, d in evs
                        if lo <= s and s + d <= hi]
    out = {"planes": [
        {"name": dev["name"], "lines": [
            {"name": "XLA Modules", "events": keep(mods)},
            {"name": "XLA Ops", "events": keep(lines["XLA Ops"])}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": keep([a, b])}]}]}
    with open(dst, "w") as f:
        json.dump({"trace": out}, f, separators=(",", ":"))
    ops = out["planes"][0]["lines"][1]["events"]
    acc, cnt = collections.Counter(), collections.Counter()
    for n, _s, d in ops:
        key = re.sub(r"[.\d]+ = ", " = ", n[:NAME], count=1)
        acc[key] += d
        cnt[key] += 1
    print(f"steps {pick}, {pick + 1} of {len(steps)}: {len(ops)} operation "
          f"events in {(hi - lo) / 1e6:.2f} ms")
    for key, ns in acc.most_common(40):
        print(f"  {ns / 1e6:8.3f} ms  x{cnt[key]:<5d} {key[:170]}")


if __name__ == "__main__":
    main(*sys.argv[1:3])

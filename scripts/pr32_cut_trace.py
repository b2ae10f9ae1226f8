#!/usr/bin/env python3
"""Cut a trace the harness kept (`BENCH_KEEP_TRACE`: the raw planes of
benchmark/lib/trace.py) to two consecutive decode programs with no prefill
between them, and the harness's `bench.step` spans round them: what a test
can hold (benchmark/tests/data/). scripts/pr30_cut_trace.py looked for two
`bench.step` spans that each hold a whole decode program; since PR 31 a
decode runs on the device while the host is a step ahead, so a program
straddles two spans and that cutter finds none. Names are cut to 260
characters, times moved to start at 0. Also prints, by name, the device
time of every operation of the cut, largest first.

    python3 scripts/pr32_cut_trace.py trace.json two_steps.json [name length]
"""
import collections
import json
import re
import sys

NAME = 260


def main(src, dst, name_len=NAME):
    NAME = int(name_len)    # (PR 40: a call's pool is its sixth operand)
    with open(src) as f:
        planes = json.load(f)["planes"]
    dev = next(p for p in planes if p["name"].startswith("/device:TPU:"))
    host = next(p for p in planes if p["name"] == "/host:CPU")
    lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
    mods = sorted(lines["XLA Modules"], key=lambda e: e[1])
    steps = sorted((e for ln in host["lines"] for e in ln["events"]
                    if e[0] == "bench.step"), key=lambda e: e[1])
    is_decode = lambda m: m[0].startswith("jit_decode")
    i = next(i for i in range(len(mods) // 2, len(mods) - 1)
             if is_decode(mods[i]) and is_decode(mods[i + 1]))
    m1, m2 = mods[i], mods[i + 1]
    lo = max(s[1] for s in steps if s[1] <= m1[1])
    hi = min(s[1] + s[2] for s in steps if s[1] + s[2] >= m2[1] + m2[2])
    keep = lambda evs: [[n[:NAME], s - lo, d] for n, s, d in evs
                        if lo <= s and s + d <= hi]
    out = {"planes": [
        {"name": dev["name"], "lines": [
            {"name": "XLA Modules", "events": keep(mods)},
            {"name": "XLA Ops", "events": keep(lines["XLA Ops"])}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": keep(steps)}]}]}
    with open(dst, "w") as f:
        json.dump({"trace": out}, f, separators=(",", ":"))
    ops = out["planes"][0]["lines"][1]["events"]
    acc, cnt = collections.Counter(), collections.Counter()
    for n, _s, d in ops:
        key = re.sub(r"[.\d]+ = ", " = ", n[:NAME], count=1)
        acc[key] += d
        cnt[key] += 1
    print(f"modules {i}, {i + 1} of {len(mods)}: "
          f"{[m[0][:24] for m in out['planes'][0]['lines'][0]['events']]}, "
          f"{len(ops)} operation events in {(hi - lo) / 1e6:.2f} ms")
    for key, ns in acc.most_common(60):
        print(f"  {ns / 1e6:8.3f} ms  x{cnt[key]:<5d} {key[:200]}")


if __name__ == "__main__":
    main(*sys.argv[1:4])

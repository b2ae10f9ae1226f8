#!/usr/bin/env python3
"""The operations of a kept trace of a training cell (`BENCH_KEEP_TRACE`,
a `--trace 1` run) by own device time, largest first, one line an
operation's text with its count: what the patterns of
`layer_metrics/*.json` are written from.

    python3 benchmark/tools/routed_train_ops.py <dir>/trace.json[.gz] [n]
    python3 benchmark/tools/routed_train_ops.py <dir>/trace.json[.gz] --cut out.json.gz

`--cut` writes what a test can hold (benchmark/tests/data/): two whole
step programs from the middle of the trace under one `bench.train_step`
span a step, names cut to 360 characters, times moved to start at 0.
"""
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib.trace import Reduced, self_times  # noqa: E402


def cut(raw, dst, name_len=360):
    planes = raw.get("trace", raw)["planes"]
    dev = next(p for p in planes if p["name"].startswith("/device:TPU:"))
    lines = {ln["name"]: ln["events"] for ln in dev["lines"]}
    mods = sorted((m for m in lines["XLA Modules"] if m[2] > 0),
                  key=lambda e: e[1])
    steps = [m for m in mods if m[0].startswith("jit_step")]
    a, b = steps[len(steps) // 2], steps[len(steps) // 2 + 1]
    lo, hi = a[1] - 1000, b[1] + b[2] + 1000
    keep = lambda evs: [[n[:name_len], s - lo, d] for n, s, d in evs
                        if lo <= s and s + d <= hi]
    out = {"planes": [
        {"name": dev["name"], "lines": [
            {"name": "XLA Modules", "events": keep(mods)},
            {"name": "XLA Ops", "events": keep(lines["XLA Ops"])}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.train_step", 0, b[1] - lo],
            ["bench.train_step", b[1] - lo, hi - b[1]]]}]}]}
    with gzip.open(dst, "wt") as f:
        json.dump({"trace": out}, f, separators=(",", ":"))
    n = len(out["planes"][0]["lines"][1]["events"])
    print(f"two steps of {a[2] / 1e6:.1f} and {b[2] / 1e6:.1f} ms, {n} "
          f"operation events, to {dst}")


def main():
    path = sys.argv[1]
    if len(sys.argv) > 3 and sys.argv[2] == "--cut":
        with (gzip.open if path.endswith(".gz") else open)(path, "rt") as f:
            return cut(json.load(f), sys.argv[3])
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 200
    with (gzip.open if path.endswith(".gz") else open)(path, "rt") as f:
        raw = json.load(f)
    t = Reduced(raw.get("trace", raw))
    acc = {}
    for dev in t.devices.values():
        for name, ns in self_times(t._in_window(dev["ops"])):
            n, s = acc.get(name, (0, 0))
            acc[name] = (n + 1, s + ns)
    busy = t.busy_s
    print(f"window {t.window_s:.3f}s busy {busy:.3f}s, "
          f"{len(acc)} distinct operations")
    for name, (n, ns) in sorted(acc.items(), key=lambda kv: -kv[1][1])[:top]:
        print(f"{ns / 1e6:9.2f} ms {100 * ns / 1e9 / busy:5.1f}% x{n:<4d} "
              f"{name[:600]}")


if __name__ == "__main__":
    main()

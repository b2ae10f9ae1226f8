#!/usr/bin/env python3
"""PR 27's chip check of the sampler's contract: one run of a serving cell
exactly as `run.py` makes it, after which every request's token stream is
written out; and the comparison of two such files, token for token.

    BENCH_STREAMS=<file> python3 benchmark/tools/calls/pr27_streams.py \
        --workload <name> --seed <n> --seconds <s> --trace 0
    python3 benchmark/tools/calls/pr27_streams.py --compare <a> <b>

A request is known by its index in the seeded stream, which fixes its
prompt, its sampling seed and its sampling parameters; its tokens are a
function of those and of the step, so two trees that sample alike emit the
same stream whatever slot or step of the window the request ran in. The
comparison runs over the tokens both sides hold (a faster tree has got
further by the window's end). Never part of the benchmark's own runs.
"""
import time

_T0 = time.perf_counter()

import json
import os
import sys
import zlib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))


def dump(run: dict, path: str):
    loop = run["loop"]
    rows = []
    for t in [*loop.finished, *loop.live.values()]:
        it = t.item
        rows.append({
            "index": it["index"], "seed": it["seed"],
            "temperature": it["temperature"], "top_p": it["top_p"],
            "top_k": it["top_k"], "max_new": it["max_new"],
            "prompt_crc": zlib.crc32(it["prompt"].tobytes()),
            "primer": bool(t.primer), "status": t.req.status,
            "generated": [int(x) for x in t.req.generated]})
    rows.sort(key=lambda r: r["index"])
    with open(path, "w") as f:
        json.dump(rows, f)
    print(f"streams: {len(rows)} requests, "
          f"{sum(len(r['generated']) for r in rows)} tokens -> {path}",
          flush=True)


def compare(path_a: str, path_b: str) -> int:
    with open(path_a) as f:
        a = {r["index"]: r for r in json.load(f)}
    with open(path_b) as f:
        b = {r["index"]: r for r in json.load(f)}
    same = ("seed", "temperature", "top_p", "top_k", "max_new",
            "prompt_crc", "primer")
    out = {k: {"requests": 0, "tokens": 0, "differ": 0}
           for k in ("sampled", "greedy")}
    first = []
    for i in sorted(set(a) & set(b)):
        ra, rb = a[i], b[i]
        if any(ra[k] != rb[k] for k in same):
            raise SystemExit(f"request {i} is not the same request on "
                             f"both sides: {ra} / {rb}")
        n = min(len(ra["generated"]), len(rb["generated"]))
        if n == 0:
            continue
        o = out["sampled" if ra["temperature"] > 0 else "greedy"]
        o["requests"] += 1
        o["tokens"] += n
        bad = [j for j in range(n)
               if ra["generated"][j] != rb["generated"][j]]
        if bad:
            o["differ"] += 1
            first.append((i, bad[0], n))
    print("STREAMS " + json.dumps({
        "a": path_a, "b": path_b, "requests_a": len(a),
        "requests_b": len(b), **out,
        "first_differences": first[:10]}), flush=True)
    return 1 if first else 0


def main(argv) -> int:
    if argv[:1] == ["--compare"]:
        return compare(argv[1], argv[2])
    from benchmark.lib import harness
    from benchmark.runners import serve, serve_hybrid
    path = os.environ["BENCH_STREAMS"]
    for runner in (serve, serve_hybrid):
        def run(ctx, inner=runner.run):
            out = inner(ctx)
            dump(out, path)
            return out
        runner.run = run
    return harness.main(argv, t_start=_T0)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

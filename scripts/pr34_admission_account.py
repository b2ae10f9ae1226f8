#!/usr/bin/env python3
"""What the admission path's spans and their stamps say about one run, in
more parts than the five metrics of ISSUE 34 carry: a builder's tool for
PERF.md section 5.

    BENCH_KEEP_TRACE=<dir> python3 benchmark/run.py --workload ... --trace 1
    python3 scripts/pr34_admission_account.py <dir>/program_spans.json

`program_spans.json` (readers/spans.py::_keep) holds the ring's spans with
`start` and `end` on the harness's clock (`time.perf_counter`) and the
stamps, as attributes, on the tracer's (`time.monotonic`). On Linux both
read CLOCK_MONOTONIC, so they are compared as they are; `outside` counts
the stamps of a kind of span that do not lie inside it to a microsecond,
and should read 0. Prints one line `ACCOUNT {...}`, milliseconds unless named.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib.stats import median as med  # noqa: E402


def main(path):
    with open(path) as f:
        doc = json.load(f)
    lo, hi = doc["window"]
    spans = [s for s in doc["spans"] if lo < s["end"] <= hi]
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    ms = lambda s: 1e3 * (s["end"] - s["start"])

    def split(name, key, keep=lambda s: True):
        """p50 of a span, of its part before the stamp, of the part after."""
        got = [s for s in by.get(name, ()) if keep(s) and key in s["attrs"]]
        before = [1e3 * (s["attrs"][key] - s["start"]) for s in got]
        after = [1e3 * (s["end"] - s["attrs"][key]) for s in got]
        return {"n": len(got), "p50": med([ms(s) for s in got]),
                "before_p50": med(before), "after_p50": med(after),
                "after_max": max(after, default=None),
                "outside": sum(1 for x in before + after if x < -1e-3)}

    steps = by.get("engine.step", [])
    decoding = {s["span_id"] for s in steps if not s["attrs"].get("idle")}
    pre = by.get("engine.prefill", [])
    dec = [d for d in by.get("engine.decode", []) if d["attrs"].get("active")]
    buckets = {}
    for p in pre:
        b = buckets.setdefault(p["attrs"]["bucket"], [0, 0, 0.0])
        b[0] += 1
        b[1] += p["attrs"]["prompt_len"] - p["attrs"]["cached_tokens"]
        b[2] += ms(p)
    out = {
        "window_s": hi - lo, "steps": len(steps),
        "idle_steps": len(steps) - len(decoding),
        "step_s": sum(ms(s) for s in steps) / 1e3,
        "prefill_s": sum(ms(p) for p in pre) / 1e3,
        "prefills": len(pre),
        "prefill_mean": sum(ms(p) for p in pre) / len(pre) if pre else None,
        "build": split("engine.build", "filled",
                       lambda s: s["parent_id"] in decoding),
        "wait": split("engine.wait", "ready"),
        "wait_without_a_decode_to_read": sum(
            1 for w in by.get("engine.wait", ())
            if w["attrs"].get("of_step") is None),
        "decodes": len(dec),
        "decodes_ahead": sum(bool(d["attrs"].get("ahead")) for d in dec),
        # bucket -> prefills, positions asked, padding %, mean ms
        "buckets": {str(k): [n, asked, round(100 * (1 - asked / (k * n)), 1),
                             round(t / n, 2)]
                    for k, (n, asked, t) in sorted(buckets.items())},
    }
    print("ACCOUNT " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])

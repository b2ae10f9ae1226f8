#!/usr/bin/env python3
"""Summary of the run logs under chiprun_out/sets/<cell>.<label>/: each
run's metrics and checks, and for each metric the median and the spread
(distance between the quartiles over the median, as
statistics.quantiles(values, n=4) gives them).

    python3 benchmark/tools/summarize.py chiprun_out/sets/<cell>.<label> [...]
"""
import glob
import json
import statistics as st
import sys


def main(dirs):
    for d in dirs:
        rows = []
        for f in sorted(glob.glob(f"{d}/seed_*.log")):
            lines = open(f).read().splitlines()
            try:
                r = json.loads(lines[-1])
            except (ValueError, IndexError):
                print(f, "NO RESULT")
                continue
            rows.append(r)
            extra = [l for l in lines if l.startswith(
                ("gap percentiles", "ttft percentiles", "compile cache ev"))]
            print(f.rsplit("/", 1)[-1], r["correct"], r["attempted"],
                  r["failed"], {k: round(v["value"], 3)
                                for k, v in r["metrics"].items()},
                  [round(c[1], 5) for c in r["checks"][-3:]
                   if isinstance(c[1], float)])
            for l in extra:
                print("    ", l[:200])
        if not rows:
            continue
        for m in rows[0]["metrics"]:
            v = [r["metrics"][m]["value"] for r in rows if m in r["metrics"]]
            if len(v) >= 2:
                q = st.quantiles(v, n=4)
                print(f"  {d.rsplit('/', 1)[-1]} {m}: median "
                      f"{st.median(v):.4f} spread "
                      f"{(q[2] - q[0]) / st.median(v):.4%} "
                      f"min {min(v):.4f} max {max(v):.4f}")


if __name__ == "__main__":
    main(sys.argv[1:])

#!/usr/bin/env python3
"""One run of a cell with something changed, for setting limits and rates.
Never part of the benchmark's own runs.

    python3 benchmark/tools/probe.py [--control fp8] [--set traffic.rate_rps=3.5]
        --workload <name> --seed <n> --seconds <s> [--trace 0|1]

`--control fp8` also runs the reference in the precision below the
configuration's and prints the number it would have been judged by (the
line starting CONTROL). `--set part.key=value` overrides one value of the
configuration (`config.`) or traffic (`traffic.`) file for this run.
"""
import time

_T0 = time.perf_counter()

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.lib import harness  # noqa: E402


def main(argv):
    control, overrides, rest = None, {}, []
    it = iter(argv)
    for a in it:
        if a == "--control":
            control = next(it)
        elif a == "--set":
            path, value = next(it).split("=", 1)
            part, *keys = path.split(".")
            node = overrides.setdefault(part, {})
            for k in keys[:-1]:
                node = node.setdefault(k, {})
            node[keys[-1]] = json.loads(value)
        else:
            rest.append(a)
    return harness.main(rest, t_start=_T0, control=control,
                        overrides=overrides)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

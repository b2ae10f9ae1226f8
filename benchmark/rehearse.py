#!/usr/bin/env python3
"""A rehearsal of one cell on the CPU at the tiny sizes each file carries
under `rehearsal`: the same harness code, end to end, for finding faults
before a chip call. It prints counts, never a time or a rate: a CPU run is
not a device metric, and a rehearsal is never a pass.

    python3 benchmark/rehearse.py --workload <name> [--seed n] [--trace 1]
"""
import time

_T0 = time.perf_counter()

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import harness  # noqa: E402

if __name__ == "__main__":
    argv = sys.argv[1:]
    if "--seed" not in argv:
        argv += ["--seed", "1"]
    if "--seconds" not in argv:
        argv += ["--seconds", "6"]
    sys.exit(harness.main(argv, t_start=_T0, rehearsal=True))

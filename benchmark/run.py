#!/usr/bin/env python3
"""The benchmark's command (BENCHMARK.json `command`):

    python3 benchmark/run.py --workload <config>.<traffic> --seed <n>
                             --seconds <s> --trace <0|1>

One cell, one run, on the chips of this machine; without them it exits
non-zero and prints no result. See benchmark/README.md.
"""
import time

_T0 = time.perf_counter()       # set-up runs from here to the window

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=_T0))

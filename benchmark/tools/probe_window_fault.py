#!/usr/bin/env python3
"""One run of a cell with the program's windowed attention, its ring or its
expert layer broken underneath (tools/window_faults.py), for reading what
`correct` makes of it. Never part of the benchmark's own runs. Other
arguments as tools/probe.py's.

    python3 benchmark/tools/probe_window_fault.py --fault ring_short
        --workload <name> --seed <n> --seconds <s>
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import probe, window_faults  # noqa: E402

if __name__ == "__main__":
    argv = sys.argv[1:]
    i = argv.index("--fault")
    name = argv[i + 1]
    with window_faults.fault(name):
        sys.exit(probe.main(argv[:i] + argv[i + 2:]))

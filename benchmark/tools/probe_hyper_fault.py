#!/usr/bin/env python3
"""One run of a cell with the program's hyper-connections, low-rank
query or YaRN scale broken underneath (tools/hyper_faults.py), for reading what
`correct` makes of it. Never part of the benchmark's own runs. Other
arguments as tools/probe.py's.

    python3 benchmark/tools/probe_hyper_fault.py --fault sinkhorn_1
        --workload <name> --seed <n> --seconds <s>
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import hyper_faults, probe  # noqa: E402

if __name__ == "__main__":
    argv = sys.argv[1:]
    i = argv.index("--fault")
    name = argv[i + 1]
    with hyper_faults.fault(name):
        sys.exit(probe.main(argv[:i] + argv[i + 2:]))

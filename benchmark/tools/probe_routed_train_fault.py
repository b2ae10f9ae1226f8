#!/usr/bin/env python3
"""One run of a cell with the trained routed decoder's band, positions,
router or share broken underneath (tools/routed_train_faults.py), for
reading what `correct` makes of it. Never part of the benchmark's own
runs. Other arguments as tools/probe.py's.

    python3 benchmark/tools/probe_routed_train_fault.py --fault band_short
        --workload <name> --seed <n> --seconds <s>
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import probe, routed_train_faults  # noqa: E402

if __name__ == "__main__":
    argv = sys.argv[1:]
    i = argv.index("--fault")
    name = argv[i + 1]
    print(f"FAULT {name}", flush=True)
    with routed_train_faults.fault(name):
        sys.exit(probe.main(argv[:i] + argv[i + 2:]))

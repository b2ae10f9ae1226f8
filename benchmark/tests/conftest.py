"""Tests of the yardstick itself. They run on the CPU at the tiny sizes
the configuration and traffic files carry under `rehearsal`:

    python3 -m pytest benchmark/tests -q
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

"""Tests of the benchmark's own files. Run by hand on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

They are no part of tier-1 (tests/). The harness is driven through its
--rehearse-rows flag, which takes the place of the look for a chip.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

"""CPU tests of the benchmark's own code: trace reduction, work counts,
the plain reference, its control and the faults the comparison catches.

    PYTHONPATH=src python -m pytest bench/tests -q
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

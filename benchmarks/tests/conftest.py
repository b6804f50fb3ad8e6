"""Tests of the benchmark's own yardstick (CPU, tiny sizes).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "benchmarks")):
    if p not in sys.path:
        sys.path.insert(0, p)

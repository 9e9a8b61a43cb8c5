"""Run by hand, outside tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

The benchmark's modules import each other by bare name (run.py puts
benchmark/ on the path); the tests do the same.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
FIXTURES = os.path.join(HERE, "fixtures")

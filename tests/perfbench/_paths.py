"""Puts the benchmark's directory on ``sys.path`` for its tests."""
import os
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(TESTS))
PERFBENCH = os.path.join(ROOT, "perfbench")
DATA = os.path.join(TESTS, "data")
TOY_BENCHMARK = os.path.join(DATA, "BENCHMARK.json")
for p in (ROOT, PERFBENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

"""Run by hand: ``python -m pytest benchmark/tests`` (CPU; tier-1's ``tests/``
does not collect this directory)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

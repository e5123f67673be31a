"""Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`` here,
or through the chip tool for the one test that needs the chip.
Not under ``tests/``, so the repository's tier-1 count is untouched."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

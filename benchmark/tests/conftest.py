"""Tests of the benchmark's own code.  They run on the CPU at tiny sizes:
`python3 -m pytest benchmark/tests -q` (tier-1's `tests/` does not collect
them)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

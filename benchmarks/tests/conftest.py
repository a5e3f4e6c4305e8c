"""Tests of the benchmark's own parts; run by hand with
`JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q` (they are not part
of the repo's tier-1 suite under tests/)."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

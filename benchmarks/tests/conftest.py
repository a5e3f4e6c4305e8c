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


def listed(manifest: dict, group: str, cell: str) -> set:
    """Names of the `group` metrics (`end_to_end`, `per_layer`) that the
    manifest lists for `cell`: looked up, never pinned to a position or a
    frozen set, so that a later PR's appended entry moves no test."""
    import run

    return {m["name"] for m in run.listed(manifest, group, cell)}


# readers that find nothing to read on a CPU: the peaks know no CPU, and a
# CPU reports no allocator peak
def reads_on_a_cpu(name: str) -> bool:
    return not name.endswith(("decide_roofline", "hbm_peak_mb"))

"""The pull loop's own Python per pull: the mean self time of the capture's
`pull` spans, each one's duration less what its child spans on that thread
cover (lock_wait, prep, dispatch, readback, demux, post, leftover...;
benchmarks/span_tree.py)."""

from cycle_math import loop_ms_per_pull

LAYER = "combiner"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return loop_ms_per_pull(scrapes, trace)

"""Milliseconds per second the directory spent rebuilding its bucket array:
`engine.directory.rebuild_ns` (a steady_clock pair inside
`rebuild_buckets()`), diff across the run's window over its seconds. The
time is inside the `prep` span of the window that tripped the rebuild; C
writes no span of its own (benchmarks/churn_math.py)."""

from churn_math import rebuild_ms_per_s

LAYER = "host prep"
SOURCE = "program_span"
UNIT = "ms/s"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return rebuild_ms_per_s(scrapes)

"""Tombstone rebuilds of the directory's bucket array inside the run's window:
`engine.directory.rebuilds`, after minus before. One is due every `nbuckets
/ 4` evictions (8.4M at 10M slots) and re-inserts every live entry under the
directory's mutex (benchmarks/churn_math.py)."""

from churn_math import directory_diff

LAYER = "host prep"
SOURCE = "program_counter"
UNIT = "count"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return directory_diff(scrapes, "rebuilds")

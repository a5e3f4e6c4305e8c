"""LRU evictions per request decided: `engine.directory.evictions` over
`engine.stats.requests`, diffs across the run's window. 1.0 where the table
is full and every request is a new key: each insert takes the least recently
used entry's slot and leaves a tombstone in the bucket array
(benchmarks/churn_math.py)."""

from churn_math import per_decision

LAYER = "host prep"
SOURCE = "program_counter"
UNIT = "evictions"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return per_decision(scrapes, "evictions")

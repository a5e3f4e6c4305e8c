"""Bytes that cross the host-device link per request decided:
`engine.stats.staged_bytes` (nbytes of the host arrays handed to the
programs) plus `fetched_bytes` (nbytes copied back) over `requests`, diffs
across the run's window. Counted in the engines' launch and fetch funnels,
whichever wire format carried the window (benchmarks/cycle_math.py)."""

from cycle_math import link_bytes_per_decision

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "bytes"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return link_bytes_per_decision(scrapes)

"""Lanes the host walks to stage a launch, per request decided:
`engine.stats.staged_lanes` over `requests`, diffs across the run's window.
`Engine._launch` adds, under the engine lock beside `staged_bytes`, the
launch's depth times the live prefix its wire-format converters walked
(times the launched width where the wide format ships whole). Beside
`window_fill` and `hot.scan_fill`, which say how much of a launched shape
held a request, this says how much of it the host touched: 1.0 is a lane a
decision, the launched width over the requests a window holds is a host
that walks what it launches. A daemon without the counter (the parent of
the change that added it) gives None (benchmarks/hot_math.py)."""

from hot_math import stat_ratio

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "lanes"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return stat_ratio(scrapes, "staged_lanes", "requests")

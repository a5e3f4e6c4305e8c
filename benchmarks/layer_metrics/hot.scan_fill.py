"""Share of the lanes the scan dispatches launched that decided a request:
`engine.stats.scan_lanes_live` over `scan_lanes` (each dispatch's depth as
launched x its width), diffs across the run's window. A round of a hot
key's tail holds a few keys and rides the ladder's bottom width all the
same (benchmarks/hot_math.py)."""

from hot_math import stat_ratio

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return stat_ratio(scrapes, "scan_lanes_live", "scan_lanes")

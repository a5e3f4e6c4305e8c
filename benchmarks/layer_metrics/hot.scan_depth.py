"""Rounds one scan dispatch retires: `engine.stats.scan_rounds` (rounds that
held a lane; the pads of a power-of-two stack are not counted) over
`scan_dispatches`, diffs across the run's window. At most 32
(`Engine._MAX_SCAN`); a hot key's rounds fill it (benchmarks/hot_math.py)."""

from hot_math import stat_ratio

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "rounds"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return stat_ratio(scrapes, "scan_rounds", "scan_dispatches")

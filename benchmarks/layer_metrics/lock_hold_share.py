"""The engine lock's utilisation: the `lock_hold` phase's total (every hold on
a serving path, acquire to release; by site in `lock_hold_sites`), diff
across the run's window, over the window's seconds. 1.0 = never free
(benchmarks/cycle_math.py)."""

from cycle_math import lock_hold_share

LAYER = "dispatch"
SOURCE = "program_span"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return lock_hold_share(scrapes)

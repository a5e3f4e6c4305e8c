"""Host preparation a call: the `prep` phase's total (`prep_ms_per_window`'s
phase: directory lookup and packing, of the columnar window and of the
leftover windows) over the calls the pull loop answered
(`front.frames_pulled`), diffs across the run's window: a call is two
engine windows and tens of rounds here, so the call is the unit
(benchmarks/hot_math.py)."""

from hot_math import phase_ms_per_call

LAYER = "host prep"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return phase_ms_per_call(scrapes, "prep")

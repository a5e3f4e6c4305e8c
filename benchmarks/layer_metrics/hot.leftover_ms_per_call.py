"""What the leftover path costs a call: the `leftover` phase's total (one
observation a chunk that handed back leftovers: the request objects built,
the router, the combiner's wait, the engine's rounds, the fill of the answer
rows) over the calls the pull loop answered (`front.frames_pulled`), diffs
across the run's window (benchmarks/hot_math.py)."""

from hot_math import phase_ms_per_call

LAYER = "combiner"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return phase_ms_per_call(scrapes, "leftover")

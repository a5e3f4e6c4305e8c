"""What a call waits for the engine lock: the `lock_wait` phase's total
(every acquisition on the serving path: the pull worker's columnar window
behind the sibling's tail, the combiner's launches) over the calls the pull
loop answered (`front.frames_pulled`), diffs across the run's window. The
slow window holds the lock across all its rounds, so this is where a
first-occurrence window's time goes (benchmarks/hot_math.py)."""

from hot_math import phase_ms_per_call

LAYER = "dispatch"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return phase_ms_per_call(scrapes, "lock_wait")

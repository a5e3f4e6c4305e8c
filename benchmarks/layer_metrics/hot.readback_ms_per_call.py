"""The wait for the device's answers and their copy back a call: the
`readback` phase's total (`readback_ms_per_window`'s phase; it contains
the wait for the device, here mostly for the scan groups) over the calls
the pull loop answered (`front.frames_pulled`), diffs across the run's
window (benchmarks/hot_math.py)."""

from hot_math import phase_ms_per_call

LAYER = "readback and demux"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return phase_ms_per_call(scrapes, "readback")

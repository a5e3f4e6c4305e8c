"""What the host does to a window's bytes inside the launch funnel before the
jitted call, per launch: the `stage` phase of /v1/debug/profile (the hot
tracker's feed, `lean_window`, `compact_window` and their refusals; on the
mesh the lean attempt), its total over its own count, diffs across the run's
window. (The staging buffer's zeroing stands before the funnel and is an
`alloc` span of the capture: benchmarks/span_tree.py prints it.)"""

from front_math import phase_mean_ms

LAYER = "dispatch"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return phase_mean_ms(scrapes, "stage")

"""Time the serving path waited with its pipeline full before it could launch
the next window, per engine window: the `queue_wait` phase's total in
/v1/debug/profile over `engine.stats.batches`, both as diffs across the
run's window. (On the columnar path of the native front the phase is fed by
the pull loop's fill stalls; 0 means it never had to wait.)"""

from scrape_math import phase_ms_per_window

LAYER = "combiner"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return phase_ms_per_window(scrapes, "queue_wait")

"""The most config rows a lean launch's table has held since boot:
`engine.stats.lean_tuples`, of the lane's 128 (a launch with more distinct
(limit, duration, algorithm, behavior) tuples leaves the lane:
`lean_refused_tuples`). Headroom, not a cost: the device's one-hot select
walks all 128 rows whatever it reads. A daemon without the counter gives
None (benchmarks/onehit_math.py)."""

from onehit_math import lean_tuples

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "rows"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return lean_tuples(scrapes)

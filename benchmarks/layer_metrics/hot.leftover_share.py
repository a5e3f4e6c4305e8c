"""Share of the items the pull loop took that a columnar chunk handed back
as leftovers: `peerlink_leftover_items_total` over `front.items_pulled`,
diffs across the run's window (benchmarks/hot_math.py says what a leftover
is and which path it takes). 0 where no key stands twice in a chunk; ~0.24
at Zipf 0.99 over 1,000 draws of 8M keys (~0.61 at an exponent of 1.2)."""

from front_math import front_counter_delta
from hot_math import metric_diff

LAYER = "combiner"
SOURCE = "program_counter"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    left = metric_diff(scrapes, "peerlink_leftover_items_total")
    items = front_counter_delta(scrapes, "items_pulled")
    if left is None or not items:
        return None
    return left / items

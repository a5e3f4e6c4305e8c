"""Engine windows a program launch carries: `engine.stats.batches` (staged
windows of at most GUBER_MAX_BATCH_WIDTH lanes, whoever formed them) over
the `launch` phase's observations (one a jitted call, stamped in the
engines' launch funnels), diffs across the run's window. 1.0 where every
window is a launch of its own; above it where the pull loop hands a pull's
run of one-call chunks to the engine as one scan group
(service/peerlink.py `_columnar_run`); below it where a window's repeated
keys ride launches of their own (the hot cell's scan groups). None on a
daemon that records no `launch` phase."""

from front_math import phase_delta
from scrape_math import engine_diff

LAYER = "combiner"
SOURCE = "program_counter"
UNIT = "windows"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    launches = phase_delta(scrapes, "launch")
    if launches is None or not launches[0]:
        return None
    return engine_diff(scrapes)["batches"] / launches[0]

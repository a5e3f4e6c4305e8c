"""Device launches per engine window: `engine.stats.rounds` over
`engine.stats.batches`. A key that occurs twice in one pull is held back
and decided in a window of its own, so duplicates show as more windows
with fewer lanes (window_fill), not as more rounds per window."""

from scrape_math import engine_diff

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "rounds"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    d = engine_diff(scrapes)
    return d["rounds"] / d["batches"] if d["batches"] else None

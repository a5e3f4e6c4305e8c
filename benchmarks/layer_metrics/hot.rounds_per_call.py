"""Rounds the engine retired for one call: `engine.stats.rounds` over the
calls the pull loop answered (`front.frames_pulled`), diffs across the
run's window. Occurrence k of a key rides round k, so a call's rounds are
its hottest key's occurrences (fewer a call where the combiner merges two
callers' leftovers into one window; benchmarks/hot_math.py)."""

from hot_math import per_call
from scrape_math import engine_diff

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "rounds"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return per_call(scrapes, engine_diff(scrapes)["rounds"])

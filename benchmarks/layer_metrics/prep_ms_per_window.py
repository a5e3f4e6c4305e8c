"""Host preparation (key directory lookup, packing) per engine window:
the `prep` phase's total in /v1/debug/profile over `engine.stats.batches`,
both as diffs across the run's window."""

from scrape_math import phase_ms_per_window

LAYER = "host prep"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return phase_ms_per_window(scrapes, "prep")

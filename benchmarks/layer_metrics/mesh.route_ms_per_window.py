"""Splitting one window by owner chip: the C pass that validates, routes
each request to its owner shard and looks its key up in that shard's
directory (`native.prep_route_columnar`), per engine window.
`engine.stats.prep_ns` over `engine.stats.batches`, both as diffs across
the run's window; on the columnar path that clock holds the C call alone."""

from mesh_math import stat_ms_per_window

LAYER = "host prep"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return stat_ms_per_window(scrapes, "prep_ns")

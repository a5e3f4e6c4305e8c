"""Packing one routed window, in Python, into the int64[R,S,9,w] buffer the
shard_map launch takes (`ShardedEngine._pack_and_decide`: a `.tolist()` of
the lane index and a loop over the owners), per engine window.
`engine.stats.pack_ns` over `engine.stats.batches`, both as diffs across
the run's window. With `mesh.route_ms_per_window` it is at most the `prep`
phase of a window."""

from mesh_math import stat_ms_per_window

LAYER = "host prep"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return stat_ms_per_window(scrapes, "pack_ns")

"""Scattering the owner blocks' answers back to their positions in the
call, one numpy index array a shard (`ShardedEngine`'s `demux` phase), per
engine window: the phase's total in /v1/debug/profile over
`engine.stats.batches`, both as diffs across the run's window."""

from mesh_math import phase_ms_per_window

LAYER = "readback and demux"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return phase_ms_per_window(scrapes, "demux")

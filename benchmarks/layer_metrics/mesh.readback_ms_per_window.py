"""The wait for the four chips' answer and its copy back per engine window
(`ShardedEngine`'s `readback` phase, which contains the wait for the
device): the phase's total in /v1/debug/profile over `engine.stats.batches`,
both as diffs across the run's window."""

from mesh_math import phase_ms_per_window

LAYER = "readback and demux"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return phase_ms_per_window(scrapes, "readback")

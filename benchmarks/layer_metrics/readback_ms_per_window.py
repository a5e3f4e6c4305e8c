"""The wait for the device's answer and its copy back per engine window
(the daemon's `readback` phase contains the wait for the device):
the `readback` phase's total in /v1/debug/profile over `engine.stats.batches`,
both as diffs across the run's window."""

from scrape_math import phase_ms_per_window

LAYER = "readback and demux"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return phase_ms_per_window(scrapes, "readback")

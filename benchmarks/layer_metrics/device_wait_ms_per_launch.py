"""The wait for the device's answer, per launch: `jax.block_until_ready` on a
launch's outputs, the `device_wait` phase of /v1/debug/profile, its total over
its own count, diffs across the run's window. The daemon makes that wait
apart from the copy only while a capture runs (it is a second release of the
GIL a window), so the mean is over the launches fetched inside the traced
run's capture. With `fetch_ms_per_launch` it is what the `readback` phase is
made of."""

from front_math import phase_mean_ms

LAYER = "readback and demux"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return phase_mean_ms(scrapes, "device_wait")

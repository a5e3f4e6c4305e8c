"""The jitted call itself, per launch: enqueue and host -> device placement of
the staged arrays, the `launch` phase of /v1/debug/profile (the pair of clock
reads `kernel_telemetry` also takes), its total over its count, diffs across
the run's window. With `stage_ms_per_launch` it is what the `dispatch` phase
is made of."""

from front_math import phase_mean_ms

LAYER = "dispatch"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return phase_mean_ms(scrapes, "launch")

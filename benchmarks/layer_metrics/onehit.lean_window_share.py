"""Share of the windows retired in the run's window that rode the 4-byte lean
lane: `kernel.windows` of /v1/debug/vars (one count per `<program>@<width>`,
kept by `KernelTelemetry.note` in every launch funnel), after minus before,
the `*_lean` programs' over all. 1.0 where every launch's lanes carry hits 1
over at most 128 (limit, duration, algorithm, behavior) tuples; 0 where one
lane of every launch does not (benchmarks/onehit_math.py)."""

from onehit_math import lean_window_share

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return lean_window_share(scrapes)

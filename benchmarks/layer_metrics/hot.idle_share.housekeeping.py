"""Share of the capture in which the device sat idle while a background
ticker's unit was open, in the repeated-key cell:
`idle_share.housekeeping`'s arithmetic (benchmarks/host_spans.py)."""

from host_spans import read_share

LAYER = "device"
SOURCE = "device_trace"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return read_share(scrapes, trace, "housekeeping")

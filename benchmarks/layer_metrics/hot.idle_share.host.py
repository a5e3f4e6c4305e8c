"""Share of the capture in which the device sat idle, no background unit
was open and a pull worker was at work: what benchmarks/host_spans.py calls
`host`. In this cell most of that time a `leftover` span is open on a pull
worker (the object path's Python, the combiner's wait, the rounds' staging)."""

from host_spans import read_share

LAYER = "device"
SOURCE = "device_trace"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return read_share(scrapes, trace, "host")

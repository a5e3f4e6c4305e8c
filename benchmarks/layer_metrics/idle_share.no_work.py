"""Share of the capture in which the device sat idle with nothing to do: no
background span open and every pull-loop worker blocked in `front.pull_wait`
(benchmarks/host_spans.py). The load, not the daemon, held the chip back."""

from host_spans import read_share

LAYER = "device"
SOURCE = "device_trace"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return read_share(scrapes, trace, "no_work")

"""The lean decide programs' share of the HBM roofline: the bytes the
algorithm needs for the lanes decided inside the capture
(`peaks.decide_bytes`: a row read and written, 4 B in, the answer out) over
the chip's peak bytes/s, over the device time they took. The lanes are the
capture's launches times the requests a launch decided (`requests` over the
`launch` phase's count: a launch is a scan group of `windows_per_launch`
windows, so `decide_roofline`'s requests over `rounds` would understate by
that factor). Bound by bytes (benchmarks/onehit_math.py)."""

from onehit_math import decide_roofline

LAYER = "device program"
SOURCE = "device_trace"
UNIT = "%"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return decide_roofline(scrapes, trace)

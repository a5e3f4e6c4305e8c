"""Share of the capture the device sat idle under a background ticker's span (a
capture that holds the cartographer's harvest reads high here: that is the
harvest, not a change's doing) in the cell whose every request is a new key:
`idle_share.housekeeping`'s arithmetic
(benchmarks/layer_metrics/idle_share.housekeeping.py); that metric lists its
cells and this one is not among them."""

from host_spans import read_share

LAYER = "device"
SOURCE = "device_trace"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return read_share(scrapes, trace, "housekeeping")

"""Share of the capture the device sat idle with nothing to do in the cell
whose every request is a new key: `idle_share.no_work`'s arithmetic
(benchmarks/layer_metrics/idle_share.no_work.py); that metric lists its
cells and this one is not among them."""

from host_spans import read_share

LAYER = "device"
SOURCE = "device_trace"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return read_share(scrapes, trace, "no_work")

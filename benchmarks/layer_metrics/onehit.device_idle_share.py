"""Share of the capture in which no operation ran on the device in the cell whose
every launch rides the lean lane: `device_idle_share`'s arithmetic
(benchmarks/layer_metrics/device_idle_share.py); that metric lists its cells
and this one is not among them."""

from layer_metrics.device_idle_share import read  # noqa: F401

LAYER = "device"
SOURCE = "device_trace"
UNIT = "share"
MOVES = "decisions_per_s"

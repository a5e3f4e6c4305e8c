"""Share of the capture in which no operation ran on the device, in the cell
whose calls repeat their keys: `device_idle_share`'s arithmetic. It says
whether the chip's rounds or the host set the pace."""

from layer_metrics.device_idle_share import read  # noqa: F401

LAYER = "device"
SOURCE = "device_trace"
UNIT = "share"
MOVES = "decisions_per_s"

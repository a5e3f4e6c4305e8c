"""Device time of one program launch in the cell whose every request is a new
key: `device_ms_per_window`'s arithmetic
(benchmarks/layer_metrics/device_ms_per_window.py); that metric lists its
cells and this one is not among them."""

from layer_metrics.device_ms_per_window import read  # noqa: F401

LAYER = "device program"
SOURCE = "device_trace"
UNIT = "ms"
MOVES = "call_p50_ms"

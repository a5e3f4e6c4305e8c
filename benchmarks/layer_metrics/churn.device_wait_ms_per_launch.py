"""The wait for the device, per launch fetched inside a capture in the cell
whose every request is a new key: `device_wait_ms_per_launch`'s arithmetic
(benchmarks/layer_metrics/device_wait_ms_per_launch.py); that metric lists
its cells and this one is not among them."""

from layer_metrics.device_wait_ms_per_launch import read  # noqa: F401

LAYER = "readback and demux"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"

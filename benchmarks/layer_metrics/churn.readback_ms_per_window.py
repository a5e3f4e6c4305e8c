"""The wait for the device's answer and its copy back per engine window in the
cell whose every request is a new key: `readback_ms_per_window`'s arithmetic
(benchmarks/layer_metrics/readback_ms_per_window.py); that metric lists its
cells and this one is not among them."""

from layer_metrics.readback_ms_per_window import read  # noqa: F401

LAYER = "readback and demux"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"

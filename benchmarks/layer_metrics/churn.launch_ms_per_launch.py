"""The jitted call's enqueue, per launch in the cell whose every request is a
new key: `launch_ms_per_launch`'s arithmetic
(benchmarks/layer_metrics/launch_ms_per_launch.py); that metric lists its
cells and this one is not among them."""

from layer_metrics.launch_ms_per_launch import read  # noqa: F401

LAYER = "dispatch"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "decisions_per_s"

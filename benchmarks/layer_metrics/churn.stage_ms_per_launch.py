"""What the host does to a window's bytes before the jitted call, per launch in
the cell whose every request is a new key: `stage_ms_per_launch`'s
arithmetic (benchmarks/layer_metrics/stage_ms_per_launch.py); that metric
lists its cells and this one is not among them."""

from layer_metrics.stage_ms_per_launch import read  # noqa: F401

LAYER = "dispatch"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "decisions_per_s"

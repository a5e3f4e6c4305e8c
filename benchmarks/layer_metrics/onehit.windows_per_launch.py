"""Engine windows a program launch carries in the cell whose every launch rides
the lean lane: `windows_per_launch`'s arithmetic
(benchmarks/layer_metrics/windows_per_launch.py); that metric lists its cells
and this one is not among them."""

from layer_metrics.windows_per_launch import read  # noqa: F401

LAYER = "combiner"
SOURCE = "program_counter"
UNIT = "windows"
MOVES = "decisions_per_s"

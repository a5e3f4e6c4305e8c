"""Calls a pull takes at once in the cell whose every request is a new key:
`frames_per_pull`'s arithmetic
(benchmarks/layer_metrics/frames_per_pull.py); that metric lists its cells
and this one is not among them."""

from layer_metrics.frames_per_pull import read  # noqa: F401

LAYER = "combiner"
SOURCE = "program_counter"
UNIT = "frames"
MOVES = "decisions_per_s"

"""Lanes decided over lanes offered in the cell whose every request is a new
key: `window_fill`'s arithmetic (benchmarks/layer_metrics/window_fill.py);
that metric lists its cells and this one is not among them."""

from layer_metrics.window_fill import read  # noqa: F401

LAYER = "combiner"
SOURCE = "program_counter"
UNIT = "share"
MOVES = "decisions_per_s"

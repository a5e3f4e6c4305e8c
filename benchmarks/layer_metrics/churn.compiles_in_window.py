"""XLA compiles inside the run's window in the cell whose every request is a
new key: `compiles_in_window`'s arithmetic
(benchmarks/layer_metrics/compiles_in_window.py); that metric lists its
cells and this one is not among them."""

from layer_metrics.compiles_in_window import read  # noqa: F401

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "compiles"
MOVES = "decisions_per_s"

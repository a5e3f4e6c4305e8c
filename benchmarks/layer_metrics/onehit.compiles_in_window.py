"""XLA compiles (or loads from the persistent cache) inside the run's window: a
lean group shape the warm-up missed would show here in the cell whose every
launch rides the lean lane: `compiles_in_window`'s arithmetic
(benchmarks/layer_metrics/compiles_in_window.py); that metric lists its cells
and this one is not among them."""

from layer_metrics.compiles_in_window import read  # noqa: F401

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "compiles"
MOVES = "decisions_per_s"

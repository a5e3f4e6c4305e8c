"""The pull loop's own Python per pull in the cell whose every request is a new
key: `loop_ms_per_pull`'s arithmetic
(benchmarks/layer_metrics/loop_ms_per_pull.py); that metric lists its cells
and this one is not among them."""

from layer_metrics.loop_ms_per_pull import read  # noqa: F401

LAYER = "combiner"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "decisions_per_s"

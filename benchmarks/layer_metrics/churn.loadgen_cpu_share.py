"""The load generators' CPU time over the window (they build 45M keys before
it, not inside it) in the cell whose every request is a new key:
`loadgen_cpu_share`'s arithmetic
(benchmarks/layer_metrics/loadgen_cpu_share.py); that metric lists its cells
and this one is not among them."""

from layer_metrics.loadgen_cpu_share import read  # noqa: F401

LAYER = "load generator"
SOURCE = "program_counter"
UNIT = "share"
MOVES = "decisions_per_s"

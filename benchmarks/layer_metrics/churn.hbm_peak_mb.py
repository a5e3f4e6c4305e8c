"""The allocator's peak on the chip since the daemon started in the cell whose
every request is a new key: `hbm_peak_mb`'s arithmetic
(benchmarks/layer_metrics/hbm_peak_mb.py); that metric lists its cells and
this one is not among them."""

from layer_metrics.hbm_peak_mb import read  # noqa: F401

LAYER = "device program"
SOURCE = "program_counter"
UNIT = "MB"
MOVES = "decisions_per_s"

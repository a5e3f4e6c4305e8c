"""Milliseconds per second the background tickers ran in the cell whose every
request is a new key: `housekeeping_ms_per_s`'s arithmetic
(benchmarks/layer_metrics/housekeeping_ms_per_s.py); that metric lists its
cells and this one is not among them."""

from layer_metrics.housekeeping_ms_per_s import read  # noqa: F401

LAYER = "housekeeping"
SOURCE = "program_span"
UNIT = "ms/s"
MOVES = "decisions_per_s"

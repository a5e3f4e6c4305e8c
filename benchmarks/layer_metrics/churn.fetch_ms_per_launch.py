"""The copy back of a launch's answers, per launch fetched inside a capture in
the cell whose every request is a new key: `fetch_ms_per_launch`'s
arithmetic (benchmarks/layer_metrics/fetch_ms_per_launch.py); that metric
lists its cells and this one is not among them."""

from layer_metrics.fetch_ms_per_launch import read  # noqa: F401

LAYER = "readback and demux"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"

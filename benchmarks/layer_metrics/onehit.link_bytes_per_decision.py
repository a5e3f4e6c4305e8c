"""Bytes that cross the host-device link per request decided (a lean window of
1000 in 8192 lanes: 32,768 B up, 131,072 B back, the config table besides) in
the cell whose every launch rides the lean lane: `link_bytes_per_decision`'s
arithmetic (benchmarks/layer_metrics/link_bytes_per_decision.py); that metric
lists its cells and this one is not among them."""

from layer_metrics.link_bytes_per_decision import read  # noqa: F401

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "bytes"
MOVES = "decisions_per_s"

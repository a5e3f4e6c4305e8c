"""Bytes staged for the programs and copied back, per request decided in the
cell whose every request is a new key: `link_bytes_per_decision`'s
arithmetic (benchmarks/layer_metrics/link_bytes_per_decision.py); that
metric lists its cells and this one is not among them."""

from layer_metrics.link_bytes_per_decision import read  # noqa: F401

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "bytes"
MOVES = "decisions_per_s"

"""Lanes the host walks to stage a launch, per request decided in the cell
whose every request is a new key: `staged_lanes_per_decision`'s arithmetic
(benchmarks/layer_metrics/staged_lanes_per_decision.py); that metric lists
its cells and this one is not among them."""

from layer_metrics.staged_lanes_per_decision import read  # noqa: F401

LAYER = "dispatch"
SOURCE = "program_counter"
UNIT = "lanes"
MOVES = "decisions_per_s"

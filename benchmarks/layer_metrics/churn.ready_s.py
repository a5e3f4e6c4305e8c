"""Seconds from the daemon's start to `Ready` (a restore of 10M rows is in it)
in the cell whose every request is a new key: `ready_s`'s arithmetic
(benchmarks/layer_metrics/ready_s.py); that metric lists its cells and this
one is not among them."""

from layer_metrics.ready_s import read  # noqa: F401

LAYER = "boot"
SOURCE = "host_clock"
UNIT = "s"
MOVES = "setup_s"

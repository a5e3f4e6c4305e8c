"""Seconds the snapshot's restore took (10M rows: the table's fill) in the cell
whose every request is a new key: `restore_s`'s arithmetic
(benchmarks/layer_metrics/restore_s.py); that metric lists its cells and
this one is not among them."""

from layer_metrics.restore_s import read  # noqa: F401

LAYER = "boot"
SOURCE = "host_clock"
UNIT = "s"
MOVES = "setup_s"

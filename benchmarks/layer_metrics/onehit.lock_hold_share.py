"""The engine lock's utilisation (the lean conversion runs under it) in the cell
whose every launch rides the lean lane: `lock_hold_share`'s arithmetic
(benchmarks/layer_metrics/lock_hold_share.py); that metric lists its cells and
this one is not among them."""

from layer_metrics.lock_hold_share import read  # noqa: F401

LAYER = "dispatch"
SOURCE = "program_span"
UNIT = "share"
MOVES = "decisions_per_s"

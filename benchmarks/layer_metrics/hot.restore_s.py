"""Seconds of `hot.ready_s` that the snapshot restore took:
`restore_s`'s reading in the cell that is not on that reader's list."""

from layer_metrics.restore_s import read  # noqa: F401

LAYER = "boot"
SOURCE = "host_clock"
UNIT = "s"
MOVES = "setup_s"

"""Seconds from starting the daemon to its `Ready` line at the width
ladder (2048..8192): `ready_s`'s reading in the cell that is not on that
reader's list. Warm it loads 33 table-sized programs from the compile cache; cold it
compiles them."""

from layer_metrics.ready_s import read  # noqa: F401

LAYER = "boot"
SOURCE = "host_clock"
UNIT = "s"
MOVES = "setup_s"

"""Seconds the snapshot restore took inside that: the Engine's constructor, from the daemon's log."""

LAYER = "boot"
SOURCE = "host_clock"
UNIT = "s"
MOVES = "setup_s"


def read(scrapes, trace):
    return scrapes["boot"]["restore_s"]

"""Seconds from starting the daemon to its `Ready` line, on the parent's clock."""

LAYER = "boot"
SOURCE = "host_clock"
UNIT = "s"
MOVES = "setup_s"


def read(scrapes, trace):
    return scrapes["boot"]["ready_s"]

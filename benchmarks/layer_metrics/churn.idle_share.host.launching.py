"""The part of the host's idle share with somebody inside `prep` or `dispatch`
in the cell whose every request is a new key: `idle_share.host.launching`'s
arithmetic (benchmarks/layer_metrics/idle_share.host.launching.py); that
metric lists its cells and this one is not among them."""

from cycle_math import read_launching_share

LAYER = "device"
SOURCE = "device_trace"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return read_launching_share(scrapes, trace)

"""The part of `idle_share.host` during which some thread is inside a `prep`
or `dispatch` span: the device idle and somebody on the way to it.
`idle_share.host` less this is idle time in which nobody is preparing a
launch (every thread is fetching, demuxing, posting or looping). A share of
the capture's window (benchmarks/cycle_math.py launching_share)."""

from cycle_math import read_launching_share

LAYER = "device"
SOURCE = "device_trace"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return read_launching_share(scrapes, trace)

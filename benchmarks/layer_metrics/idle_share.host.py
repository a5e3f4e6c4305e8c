"""Share of the capture in which the device sat idle and neither of the other
two explains it: a serving span was open (the host was preparing, posting
or waiting for a lock) or none was. With `idle_share.no_work` and
`idle_share.housekeeping` it sums to `device_idle_share`
(benchmarks/host_spans.py)."""

from host_spans import read_share

LAYER = "device"
SOURCE = "device_trace"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return read_share(scrapes, trace, "host")

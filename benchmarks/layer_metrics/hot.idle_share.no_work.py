"""Share of the capture in which the device sat idle with nothing to do
(every pull worker blocked in `front.pull_wait`, no background span open)
in the repeated-key cell: `idle_share.no_work`'s arithmetic
(benchmarks/host_spans.py). With `hot.idle_share.housekeeping` and
`hot.idle_share.host` it sums to `hot.device_idle_share`."""

from host_spans import read_share

LAYER = "device"
SOURCE = "device_trace"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return read_share(scrapes, trace, "no_work")

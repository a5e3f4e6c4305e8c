"""Share of the capture in which the chips sat idle with no background
unit open and a pull worker at work: the serving path held them back (a
span of `ShardedEngine`'s, `lock_wait`, `prep`, `dispatch`, `readback`,
`demux`, or the front's `post`, was open, or none was). What
benchmarks/host_spans.py calls `host`, averaged over the device planes; of
`mesh.device_idle_share` the rest is housekeeping and waiting for work."""

from host_spans import read_share

LAYER = "device"
SOURCE = "device_trace"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return read_share(scrapes, trace, "host")

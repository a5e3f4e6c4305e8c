"""Device time of one engine window of the lean programs: the capture's summed
device-op time over its launches times `windows_per_launch` (a launch is a
scan group; `device_ms_per_window` divides by launches alone and so reads a
group's time) (benchmarks/onehit_math.py)."""

from onehit_math import device_ms_per_window

LAYER = "device program"
SOURCE = "device_trace"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return device_ms_per_window(scrapes, trace)

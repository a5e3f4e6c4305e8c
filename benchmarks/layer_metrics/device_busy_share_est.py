"""Share of the run's whole window in which the device ran a program, by
count: device launches inside the window (`engine.stats.rounds` diff) x the
device time of one launch (from the trace) / window seconds. The capture
itself slows the daemon (the profiler's Python tracer), so the traced two
seconds are idler than the rest; this estimate is not."""

from scrape_math import engine_diff

LAYER = "device"
SOURCE = "device_trace"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    if not trace or not trace["launches"]:
        return None
    per_launch_s = trace["busy_s"] / trace["launches"]
    return engine_diff(scrapes)["rounds"] * per_launch_s / scrapes["window_s"]

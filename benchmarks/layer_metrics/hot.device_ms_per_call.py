"""Device time one call costs: the device's busy time inside the capture
over the calls the front's pull loop took between the capture's two edges
(`capture.last_rates.frames_pulled_in`, counted by the daemon where it
starts and stops the trace), single windows and scan groups alike
(benchmarks/hot_math.py)."""

from hot_math import in_capture

LAYER = "device program"
SOURCE = "device_trace"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    n = in_capture(scrapes, trace, "frames_pulled_in")
    if n is None:
        return None
    return trace["busy_s"] / n * 1e3

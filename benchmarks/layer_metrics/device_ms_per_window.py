"""Device time of one program launch: summed device-op time over the
program launches inside the capture."""

LAYER = "device program"
SOURCE = "device_trace"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    if not trace or not trace["launches"]:
        return None
    return trace["busy_s"] / trace["launches"] * 1e3

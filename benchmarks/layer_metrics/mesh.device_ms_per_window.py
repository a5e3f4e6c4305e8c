"""Device time of one shard_map launch on one chip: summed device-op time
over the program launches inside the capture, the mean over the four
device planes (trace_reduce averages both over the devices it finds)."""

LAYER = "device program"
SOURCE = "device_trace"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    if not trace or not trace["launches"]:
        return None
    return trace["busy_s"] / trace["launches"] * 1e3

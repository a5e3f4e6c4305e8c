"""Share of the capture in which no operation ran on a chip, the mean over
the four device planes: 1 - union of device-op intervals / capture."""

LAYER = "device"
SOURCE = "device_trace"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    if not trace or not trace["window_s"]:
        return None
    return 1.0 - trace["busy_s"] / trace["window_s"]

"""The decide programs' share of the HBM roofline: the bytes the algorithm
needs for the lanes decided inside the capture (peaks.decide_bytes) over the
chip's peak bytes/s, over the device time they took. Bound by bytes: the
program does a few integer operations a lane."""

import peaks
from scrape_math import engine_diff

LAYER = "device program"
SOURCE = "device_trace"
UNIT = "%"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    d = engine_diff(scrapes)
    if not trace or not trace["busy_s"] or not d["rounds"]:
        return None
    lanes = trace["launches"] * d["requests"] / d["rounds"]
    least_s = peaks.decide_bytes(lanes) \
        / peaks.peak(scrapes["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]

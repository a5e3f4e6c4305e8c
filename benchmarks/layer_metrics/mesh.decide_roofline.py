"""The sharded decide program's share of one chip's HBM roofline: the bytes
the algorithm needs (peaks.decide_bytes) for the lanes ONE chip decided
inside the capture, over that chip's peak bytes/s, over that chip's busy
time. A launch carries a window's requests split over the shards, so a
chip's lanes are launches x requests a round / shards; busy time is the
mean over the device planes. Bound by bytes: the program does a few integer
operations a lane. The sharded decide program is the one kernel here."""

import peaks
from mesh_math import shards
from scrape_math import engine_diff

LAYER = "device program"
SOURCE = "device_trace"
UNIT = "%"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    d = engine_diff(scrapes)
    n = shards(scrapes)
    if not trace or not trace["busy_s"] or not d["rounds"] or not n:
        return None
    lanes = trace["launches"] * d["requests"] / d["rounds"] / n
    least_s = peaks.decide_bytes(lanes) \
        / peaks.peak(scrapes["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]

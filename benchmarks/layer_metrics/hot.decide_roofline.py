"""The decide programs' share of the HBM roofline where launches scan: the
bytes the algorithm needs (peaks.decide_bytes) for the LIVE lanes decided
inside the capture, over the chip's peak bytes/s, over the chip's busy
time. The live lanes are the requests the front's pull loop took between
the capture's two edges (`capture.last_rates.items_pulled_in`; each is
decided once, in a single window or a scan group); `decide_roofline` takes
launches x requests a round of the whole window, which undercounts as soon
as one launch carries 32 rounds. A pad lane needs no byte, so the share
cannot pass 100. Bound by bytes. No kernel is new here: the programs are
`decide_packed*` and `decide_scan_packed*` (ops/decide.py), as in
`node10m.herd100`."""

import peaks
from hot_math import in_capture

LAYER = "device program"
SOURCE = "device_trace"
UNIT = "%"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    lanes = in_capture(scrapes, trace, "items_pulled_in")
    if lanes is None:
        return None
    least_s = peaks.decide_bytes(lanes) \
        / peaks.peak(scrapes["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]

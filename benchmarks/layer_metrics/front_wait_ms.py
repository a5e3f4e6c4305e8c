"""Time a call's frame sat in the native front's queue before a pull-loop
worker took it, mean per frame: the `front_wait` phase of
/v1/debug/profile (stamped in native/peerlink.cpp from the frame's last
parsed byte to `pls_next_batch`), total over count, as diffs across the
run's window."""

from front_math import phase_mean_ms

LAYER = "wire front"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return phase_mean_ms(scrapes, "front_wait")

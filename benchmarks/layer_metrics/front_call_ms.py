"""Server-side residency of one call, mean per frame: the `front_call`
phase of /v1/debug/profile, from the frame's last parsed byte to its reply
written (native/peerlink.cpp). What the client's latency is, less the
loopback and the client itself."""

from front_math import phase_mean_ms

LAYER = "wire front"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return phase_mean_ms(scrapes, "front_call")

"""The 90th percentile of one call in the open-loop cell, from its due
instant, in the traced run: the calls that queued behind a stall of the
daemon, below the tail that the longest stall sets."""

LAYER = "load generator"
SOURCE = "host_clock"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return scrapes["latency_ms"]["p90"]

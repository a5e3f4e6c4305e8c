"""The longest call of the run's window, from the load generators' clocks: the
stall a caller feels when a window's `prep` holds a tombstone rebuild (or a
harvest holds the engine lock). One sample, so it is a per-layer reading and
no end-to-end metric."""

LAYER = "load generator"
SOURCE = "host_clock"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return scrapes["latency_ms"].get("max")

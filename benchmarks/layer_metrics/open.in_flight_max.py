"""The deepest backlog a stall left: the most calls due and not yet answered
at one instant of the window, over all clients of the open loop. By
Little's law the mean is rate x mean latency (a few calls); the maximum is
what the window's longest stall queued behind it."""

LAYER = "load generator"
SOURCE = "host_clock"
UNIT = "calls"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return scrapes["loadgen"].get("in_flight_max")

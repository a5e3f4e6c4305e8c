"""How late the open loop's generator ran, ms: the 99th percentile, over the
calls due in the window, of the instant a call was sent minus the instant
the schedule made it due. Near 0, or the cell measures the generator: a
call's latency counts from its due instant, so the lag is inside it."""

LAYER = "load generator"
SOURCE = "host_clock"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    lag = scrapes["loadgen"].get("send_lag_ms")
    return None if lag is None else lag["p99"]

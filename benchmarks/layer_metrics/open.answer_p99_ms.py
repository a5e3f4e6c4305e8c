"""The daemon's part of the open loop's tail, ms: the 99th percentile, over
the calls due in the window, of answer received minus SENT. A call's latency
there counts from its due instant, so it is `open.send_lag_ms`'s part (the
generator's, and the host's that holds it) plus this one: a stall of the
daemon reads here, a stall of the generators there."""

LAYER = "load generator"
SOURCE = "host_clock"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    answer = scrapes["loadgen"].get("answer_ms")
    return None if answer is None else answer["p99"]

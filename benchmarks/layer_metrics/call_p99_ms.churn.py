"""The 99th percentile of one call in the cell whose every request is a new
key, from the traced run (as `call_p99_ms.herd`): the end-to-end
`call_p99_ms` lists its cells and this one is not among them."""

LAYER = "load generator"
SOURCE = "host_clock"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return scrapes["latency_ms"]["p99"]

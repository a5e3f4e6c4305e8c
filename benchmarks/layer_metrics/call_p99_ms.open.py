"""The 99th percentile of one call in the open-loop cell, from its due
instant, in the traced run. What the cell exists for, and no end-to-end
metric there: see PERF.md section 2 for the readings that decided it."""

LAYER = "load generator"
SOURCE = "host_clock"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return scrapes["latency_ms"]["p99"]

"""The 99th percentile of one call in the herd cell, from the traced run.
It is no end-to-end metric there: the daemon's audit ticker stalls serving
for most of a second every few seconds, the stalled calls are 1.2-1.5% of
all, and the 99th percentile lands on whichever side of that edge a run
falls (670 or 860 ms in runs of the same code, PERF.md section 6, PR 24)."""

LAYER = "load generator"
SOURCE = "host_clock"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return scrapes["latency_ms"]["p99"]

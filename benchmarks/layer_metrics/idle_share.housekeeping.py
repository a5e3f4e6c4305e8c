"""Share of the capture in which the device sat idle while one of the daemon's
background tickers ran (a `bg:*` span open: the ledger audit, the anomaly
sweep...; benchmarks/host_spans.py)."""

from host_spans import read_share

LAYER = "device"
SOURCE = "device_trace"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    return read_share(scrapes, trace, "housekeeping")

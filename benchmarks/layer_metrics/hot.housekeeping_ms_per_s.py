"""Milliseconds per second the daemon's background tickers ran in the
repeated-key cell: `housekeeping_ms_per_s`'s arithmetic. The audit has few
lanes a second to fold here and many a key."""

from layer_metrics.housekeeping_ms_per_s import read  # noqa: F401

LAYER = "housekeeping"
SOURCE = "program_span"
UNIT = "ms/s"
MOVES = "decisions_per_s"

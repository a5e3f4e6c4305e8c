"""The longest tombstone rebuild since boot, ms:
`engine.directory.rebuild_max_ns` at the window's closing scrape (the
directory keeps a maximum, not a series; the warm traffic's first rebuild is
in it). Every caller in flight waits it out: the directory's mutex and the
engine lock are held (benchmarks/churn_math.py)."""

from churn_math import rebuild_max_ms

LAYER = "host prep"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "call_p50_ms"


def read(scrapes, trace):
    return rebuild_max_ms(scrapes)

"""Harvests of the keyspace cartographer closed by the window's closing scrape,
since boot: the `keyspace.harvest` site's count in /v1/debug/profile
`bg_sites`. Each holds the whole hit column of the table on the host for a
moment (+335 to +390 MB at 10M rows), and `daemon_rss_mb` is the largest
resident set sampled up to right after that scrape: runs that differ in this
count are not reading the same peak (benchmarks/churn_math.py)."""

from churn_math import harvests

LAYER = "housekeeping"
SOURCE = "program_counter"
UNIT = "count"
MOVES = "daemon_rss_mb"


def read(scrapes, trace):
    return harvests(scrapes)

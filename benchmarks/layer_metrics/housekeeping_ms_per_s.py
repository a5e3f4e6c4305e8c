"""Milliseconds per second the daemon's background tickers ran (the anomaly
sweep, the ledger audit and its slot resolution, history samples, keyspace
harvests...): the `bg_sites` of /v1/debug/profile, each site's own time,
summed, as a diff across the run's window over its seconds. They take the
GIL and the engine lock from the serving threads."""

LAYER = "housekeeping"
SOURCE = "program_span"
UNIT = "ms/s"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    after = scrapes["after"]["profile"].get("bg_sites")
    before = scrapes["before"]["profile"].get("bg_sites")
    if after is None or before is None:
        return None
    ns = sum(snap["total_ns"] - before.get(site, {}).get("total_ns", 0)
             for site, snap in after.items())
    return ns / 1e6 / scrapes["window_s"]

"""Decisions answered for the calls due in the window over the decisions
those calls offered. 1.0 while the daemon keeps up with the open loop's
rate; under it, calls failed, timed out or were never answered."""

LAYER = "load generator"
SOURCE = "host_clock"
UNIT = "share"
MOVES = "decisions_per_s"


def read(scrapes, trace):
    lg = scrapes["loadgen"]
    if not lg.get("offered_decisions"):
        return None
    return lg["answered_decisions"] / lg["offered_decisions"]

"""Diffs of what the daemon's profiler reports from PR 25 on, across the
run's window: the native front's histograms and counters, and the
background tickers' sites (`/v1/debug/profile`, schema 2). A daemon from
before that change reports none of them, and every function here then
returns None."""


def phase_delta(scrapes: dict, phase: str):
    """(observations, nanoseconds) a /v1/debug/profile phase gained."""
    a = scrapes["after"]["profile"]["phases"].get(phase)
    b = scrapes["before"]["profile"]["phases"].get(phase)
    if a is None or b is None:
        return None
    return a["n"] - b["n"], a["total_ns"] - b["total_ns"]


def phase_mean_ms(scrapes: dict, phase: str):
    d = phase_delta(scrapes, phase)
    if d is None or not d[0]:
        return None
    return d[1] / d[0] / 1e6


def front_counter_delta(scrapes: dict, name: str):
    a = scrapes["after"]["profile"].get("front")
    b = scrapes["before"]["profile"].get("front")
    if a is None or b is None or name not in a:
        return None
    return a[name] - b[name]

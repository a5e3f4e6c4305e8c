"""Arithmetic the `mesh.*` readers share: what `ShardedEngine` counts in
`engine.stats` (/v1/debug/vars) and stamps on its profiler, as diffs across
the run's window. A daemon that does not record a counter or a phase (the
parent of the change that added it) gives None, never an exception."""

import scrape_math
from scrape_math import engine_diff


def stat_diff(scrapes: dict, key: str):
    """`engine.stats[key]`, after minus before; None where it is absent."""
    a = scrapes["after"]["vars"]["engine"]["stats"].get(key)
    b = scrapes["before"]["vars"]["engine"]["stats"].get(key)
    if a is None or b is None:
        return None
    return a - b


def stat_ms_per_window(scrapes: dict, key: str):
    """One of the engine's private `*_ns` clocks over the engine windows."""
    windows = engine_diff(scrapes)["batches"]
    ns = stat_diff(scrapes, key)
    if not windows or ns is None:
        return None
    return ns / windows / 1e6


def phase_ms_per_window(scrapes: dict, phase: str):
    """scrape_math.phase_ms_per_window, or None where nothing was observed
    in the window (an engine without stamps leaves the Instance's fallback
    profiler empty)."""
    a = scrapes["after"]["profile"]["phases"].get(phase)
    b = scrapes["before"]["profile"]["phases"].get(phase)
    if not a or not b or a["n"] == b["n"]:
        return None
    return scrape_math.phase_ms_per_window(scrapes, phase)


def shards(scrapes: dict):
    """Chips the table is sharded over."""
    return scrapes["after"]["vars"]["engine"]["device"].get("device_count")

"""Arithmetic that several per-layer readers share: diffs of the daemon's
counters and phase clocks across the run's window."""


def engine_diff(scrapes: dict) -> dict:
    """`engine.stats` counters, after minus before. `batches` are engine
    windows: staged batches of at most GUBER_MAX_BATCH_WIDTH lanes, whoever
    formed them (the native front's pull loop or the Python combiner);
    `rounds` are the device launches."""
    a = scrapes["after"]["vars"]["engine"]["stats"]
    b = scrapes["before"]["vars"]["engine"]["stats"]
    return {k: a[k] - b[k] for k in ("requests", "batches", "rounds")}


def phase_ms_per_window(scrapes: dict, phase: str):
    """A /v1/debug/profile phase's total over the engine windows, ms."""
    windows = engine_diff(scrapes)["batches"]
    if not windows:
        return None
    a = scrapes["after"]["profile"]["phases"][phase]
    b = scrapes["before"]["profile"]["phases"][phase]
    return (a["total_ns"] - b["total_ns"]) / windows / 1e6

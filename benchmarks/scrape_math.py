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


def device_peak_bytes(scrape: dict):
    """The allocator's peak on the fullest chip since the daemon started
    (`engine.device.memory[].peak_bytes_in_use` of one scrape), or None
    where the daemon reports none (a CPU)."""
    memory = scrape["vars"]["engine"]["device"].get("memory")
    peaks = [m.get("peak_bytes_in_use") for m in memory or []]
    return max((p for p in peaks if p is not None), default=None)


def background_units(before: dict, after: dict) -> dict:
    """{site: [units, ms of the site's own time]} that the daemon's
    background tickers ran between two scrapes (`bg_sites` of
    /v1/debug/profile); the sites that ran none are left out."""
    a = after["profile"].get("bg_sites") or {}
    b = before["profile"].get("bg_sites") or {}
    out = {}
    for site, snap in a.items():
        n = snap["n"] - b.get(site, {}).get("n", 0)
        if n:
            ns = snap["total_ns"] - b.get(site, {}).get("total_ns", 0)
            out[site] = [n, round(ns / 1e6, 1)]
    return out


def phase_ms_per_window(scrapes: dict, phase: str):
    """A /v1/debug/profile phase's total over the engine windows, ms."""
    windows = engine_diff(scrapes)["batches"]
    if not windows:
        return None
    a = scrapes["after"]["profile"]["phases"][phase]
    b = scrapes["before"]["profile"]["phases"][phase]
    return (a["total_ns"] - b["total_ns"]) / windows / 1e6

"""Arithmetic the `churn.*` readers share: what the daemon counts where
every request is a key it has never seen and the table is full, as diffs
across the run's window.

The host directory (`gubernator_tpu/native/keydir.cpp`) gives a new key a
slot in `allocate()`: from the free stack while there is one, else the
least recently used entry's, whose bucket entry becomes a tombstone; once
a quarter of the bucket array is tombstones `rebuild_buckets()` re-inserts
every live entry, under the directory's mutex and inside the window's
`prep`. It counts `evictions`, `inserts` (keys given a slot: a serving
path's `fresh` lanes, and a restore's rows before the window),
`rebuilds`, `rebuild_ns` and `rebuild_max_ns` (the longest rebuild since
boot), which `/v1/debug/vars` shows as `engine.directory`. The
cartographer's harvests are the `keyspace.harvest` site of
`/v1/debug/profile` `bg_sites`; the ledger's verdicts are
`ledger.violations` in `/v1/debug/vars`.

A daemon that shows no `engine.directory` (the parent of the change that
added it) gives None from every function that reads it, never an
exception."""

from mesh_math import stat_diff  # engine.stats[key] diff, None where absent


def directory(scrapes: dict, which: str, key: str):
    """`engine.directory[key]` of one scrape; None where absent."""
    section = scrapes[which]["vars"]["engine"].get("directory") or {}
    return section.get(key)


def directory_diff(scrapes: dict, key: str):
    a, b = directory(scrapes, "after", key), directory(scrapes, "before", key)
    if a is None or b is None:
        return None
    return a - b


def per_decision(scrapes: dict, key: str):
    """A directory counter's diff over the requests the engine decided."""
    n, decided = directory_diff(scrapes, key), stat_diff(scrapes, "requests")
    if n is None or not decided:
        return None
    return n / decided


def rebuild_ms_per_s(scrapes: dict):
    ns = directory_diff(scrapes, "rebuild_ns")
    if ns is None or not scrapes["window_s"]:
        return None
    return ns / 1e6 / scrapes["window_s"]


def rebuild_max_ms(scrapes: dict):
    """The longest rebuild since boot (the directory keeps a maximum, not a
    series); None before the first one."""
    ns = directory(scrapes, "after", "rebuild_max_ns")
    if not ns:
        return None
    return ns / 1e6


def harvests(scrapes: dict):
    """`keyspace.harvest` units closed by the window's closing scrape, since
    boot: each holds the whole hit column on the host for a moment, and the
    resident set's peak is read right after that scrape."""
    sites = scrapes["after"]["profile"].get("bg_sites")
    if sites is None:
        return None
    return (sites.get("keyspace.harvest") or {}).get("n", 0)


def ledger_violations(scrapes: dict):
    a = (scrapes["after"]["vars"].get("ledger") or {}).get("violations")
    b = (scrapes["before"]["vars"].get("ledger") or {}).get("violations")
    if a is None or b is None:
        return None
    return a - b

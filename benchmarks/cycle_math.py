"""Arithmetic the readers of the launch cycle share: the engine lock's
hold in /v1/debug/profile (schema 4), the link's two counters in
`engine.stats` (/v1/debug/vars), and the spans of a capture, all as diffs
across the run's window. (The four sub-phases need none: a reader of one
is `front_math.phase_mean_ms` over the phase's own observations.)

Both engines stamp inside the funnels every launch and every fetch goes
through (models/engine.py `_launch` / `_fetch_staged`,
parallel/sharded.py `_launch_mesh` / `_fetch_mesh`): `stage` and `launch`
are what `dispatch` is made of, once a launch; `device_wait` and `fetch`
what `readback` is made of, once a launch fetched while a capture runs
(telling them apart takes a wait of its own, which the daemon makes only
then). A daemon that records none of this (the parent of the change that
added it) gives None from every function here, never an exception."""

from front_math import phase_delta
from mesh_math import stat_diff  # engine.stats[key] diff, None where absent
import host_spans
import span_tree

LAUNCHING = ("prep", "dispatch")  # on its way to the chip


def link_bytes_per_decision(scrapes: dict):
    """Bytes staged for the programs and copied back from them, over the
    requests decided."""
    up = stat_diff(scrapes, "staged_bytes")
    down = stat_diff(scrapes, "fetched_bytes")
    decided = stat_diff(scrapes, "requests")
    if up is None or down is None or not decided:
        return None
    return (up + down) / decided


def lock_hold_share(scrapes: dict):
    """The engine lock's utilisation: the time it was held on a serving
    path between the two scrapes over the run's window (1.0 = never
    free). The callers send for exactly the window, so the holds between
    the scrapes are the window's, also where the second scrape comes late
    (behind a capture that took 20 s to write: the hot cell's)."""
    held = phase_delta(scrapes, "lock_hold")
    if held is None or not scrapes["window_s"]:
        return None
    return held[1] / 1e9 / scrapes["window_s"]


def loop_ms_per_pull(scrapes: dict, trace: dict):
    """Mean self time of the capture's `pull` spans, ms."""
    path = span_tree.capture_path(scrapes, trace)
    if path is None:
        return None
    return span_tree.self_time_mean_ms(
        span_tree.forest(span_tree.load(path)), "pull")


def launching_share(ops, spans, busy_s: float, window_s: float):
    """The part of `idle_share.host` during which some thread is inside a
    `prep` or `dispatch` span. `host_spans.split_idle` gives every idle
    instant to `housekeeping`, `no_work` or `host`, and to `housekeeping`
    first: so with the launching spans handed to it as one more
    background site, `host` falls by exactly the idle time that was
    `host` and had somebody on the way to the chip. No second copy of
    its bounds and regions, and never more than `idle_share.host`."""
    real = host_spans.split_idle(ops, spans, busy_s, window_s)
    if real is None:
        return None
    relabelled = [(t, host_spans.BACKGROUND + name if name in LAUNCHING
                   else name, s, e) for t, name, s, e in spans]
    return real["host"] - host_spans.split_idle(
        ops, relabelled, busy_s, window_s)["host"]


def read_launching_share(scrapes: dict, trace: dict):
    """`idle_share.host.launching` of a traced run. None on a daemon from
    before the launch funnels were stamped: its combiner's thread and its
    scan tail write no `prep` or `dispatch` span, so it would read low by
    that thread's share."""
    path = span_tree.capture_path(scrapes, trace)
    if path is None or phase_delta(scrapes, "launch") is None:
        return None
    ops, spans = host_spans.load(path)  # cached: idle_share.* read it too
    return launching_share(ops, spans, trace["busy_s"], trace["window_s"])

"""Arithmetic the `hot.*` readers share: what the daemon counts and stamps
on the path a repeated key takes, as diffs across the run's window.

A key that stands more than once in a columnar chunk is packed once, at its
first occurrence; the C prep hands every later occurrence back as a
leftover. `service/peerlink.py _leftover_items` builds one request object
for each and sends them through the public path (`Instance.get_rate_limits`,
the router, `service/combiner.py`, `Engine.launch_windows`), where
`models/prep.py` splits them into rounds: occurrence k of a key rides round
k, and the rounds retire in scan groups of up to 32, each `min_width` lanes
wide (`Engine._apply_windows_scanned`).

The daemon meters that path as: `peerlink_leftover_items_total`
(/metrics), the `leftover` phase (/v1/debug/profile) with a host span of
the same name in a capture, `scan_dispatches`, `scan_rounds`,
`scan_lanes_live`, `scan_lanes` in `engine.stats` (/v1/debug/vars), and
what the front's pull loop took between a capture's two edges
(`capture.last_rates`: `frames_pulled_in`, `items_pulled_in`). A daemon
that records none of them (the parent of the change that added them) gives
None from every function here, never an exception."""

from front_math import front_counter_delta, phase_delta
from mesh_math import stat_diff  # engine.stats[key] diff, None where absent


def metric_diff(scrapes: dict, family: str):
    """A /metrics counter family, after minus before; None where the
    daemon does not export it."""
    a = scrapes["after"]["metrics"].get(family)
    b = scrapes["before"]["metrics"].get(family)
    if a is None or b is None:
        return None
    return a - b


def calls(scrapes: dict):
    """Calls the pull loop answered in the window (`front.frames_pulled`:
    a frame is one GetRateLimits call)."""
    return front_counter_delta(scrapes, "frames_pulled")


def per_call(scrapes: dict, amount):
    n = calls(scrapes)
    if amount is None or not n:
        return None
    return amount / n


def stat_ratio(scrapes: dict, over: str, under: str):
    """engine.stats[over] / engine.stats[under], both as diffs."""
    a, b = stat_diff(scrapes, over), stat_diff(scrapes, under)
    if a is None or not b:
        return None
    return a / b


def phase_ms_per_call(scrapes: dict, phase: str):
    """A /v1/debug/profile phase's total over the calls answered, ms."""
    d = phase_delta(scrapes, phase)
    return per_call(scrapes, None if d is None else d[1] / 1e6)


def in_capture(scrapes: dict, trace: dict, counter: str):
    """What the front's pull loop took between the edges of the capture
    that `trace` was reduced from: `frames_pulled_in` (calls) or
    `items_pulled_in` (requests), read by the daemon's profiler where it
    starts and stops the capture, so the count and the device's busy time
    cover the same stretch. A call pulled before one edge and decided
    after it is counted on one side only; a capture holds tens of calls."""
    if not trace or not trace.get("busy_s"):
        return None
    capture = scrapes["after"]["profile"].get("capture") or {}
    if capture.get("last_mode") != "jax_trace":
        return None
    return (capture.get("last_rates") or {}).get(counter) or None

"""Arithmetic the `onehit.*` readers share: which wire format and which
device programs served the window, why a launch left the lean lane, and what
a lean launch cost the chip, as diffs across the run's window.

`Engine._launch` (gubernator_tpu/models/engine.py) tries the 4-byte lean
lane first on every launch under GUBER_STAGING=auto: `lean_stage`
(gubernator_tpu/ops/decide.py) converts the launch's live prefix to one i32
word a lane and an i64[128, 4] table of (limit, duration, algorithm,
behavior) rows, or refuses and says why. The daemon meters that as:
`kernel.windows` of /v1/debug/vars (windows retired per `<program>@<width>`,
process-wide; the lean programs are `packed_lean`, `scan_lean` and
`carry_lean`), and in `engine.stats` the five `lean_refused_<reason>`
counters (launches the lane refused, by the first reason that held:
capacity, hits, gregorian, range, tuples) and `lean_tuples` (the most
config rows a lean launch's table has held since boot, of 128). The
`launch` phase of /v1/debug/profile counts the launches.

A daemon that records none of a reader's counters (the parent of the change
that added `lean_refused_*` and `lean_tuples`; `ShardedEngine`, which keeps
neither) gives None from that function, never an exception."""

import peaks
from front_math import phase_delta
from mesh_math import stat_diff  # engine.stats[key] diff, None where absent
from scrape_math import engine_diff

# ops/decide.py LEAN_REFUSALS, by name: the benchmark imports nothing of the
# program
REFUSALS = ("capacity", "hits", "gregorian", "range", "tuples")


def kernel_windows(scrapes: dict):
    """{`<program>@<width>`: windows retired in the run's window}; None
    where the daemon shows no `kernel` section."""
    a = (scrapes["after"]["vars"].get("kernel") or {}).get("windows")
    b = (scrapes["before"]["vars"].get("kernel") or {}).get("windows")
    if a is None or b is None:
        return None
    return {k: n - b.get(k, 0) for k, n in a.items()}


def lean_window_share(scrapes: dict):
    """Windows the lean programs retired over the windows any program
    retired."""
    windows = kernel_windows(scrapes)
    if not windows or not sum(windows.values()):
        return None
    lean = sum(n for k, n in windows.items()
               if k.split("@")[0].endswith("_lean"))
    return lean / sum(windows.values())


def lean_tuples(scrapes: dict):
    """The most config rows a lean launch's table has held since boot."""
    return scrapes["after"]["vars"]["engine"]["stats"].get("lean_tuples")


def launches(scrapes: dict):
    """Program launches in the run's window (the `launch` phase's
    observations: one a jitted call); None where none was observed."""
    d = phase_delta(scrapes, "launch")
    return d[0] if d and d[0] else None


def lean_refused_per_launch(scrapes: dict):
    """Launches the lean lane refused, whatever the reason, over the
    launches made."""
    refused = [stat_diff(scrapes, "lean_refused_" + why) for why in REFUSALS]
    n = launches(scrapes)
    if None in refused or n is None:
        return None
    return sum(refused) / n


def lanes_in_capture(scrapes: dict, trace: dict):
    """Requests decided inside the capture: its launches times the
    requests a launch decided over the run's window. (Not `requests` over
    `rounds`: a launch carries `windows_per_launch` rounds.)"""
    n = launches(scrapes)
    if not trace or not trace.get("launches") or n is None:
        return None
    return trace["launches"] * engine_diff(scrapes)["requests"] / n


def decide_roofline(scrapes: dict, trace: dict):
    """The programs' share of the HBM roofline, %: the bytes the algorithm
    needs for the lanes decided inside the capture (`peaks.decide_bytes`,
    which reckons the 4-byte lean lane in) over the chip's peak bytes/s,
    over the device time they took. Bound by bytes."""
    lanes = lanes_in_capture(scrapes, trace)
    if lanes is None or not trace["busy_s"]:
        return None
    least_s = peaks.decide_bytes(lanes) \
        / peaks.peak(scrapes["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]


def device_ms_per_window(scrapes: dict, trace: dict):
    """Device time of one engine window: the capture's busy time over its
    launches times the windows a launch carried over the run's window."""
    n = launches(scrapes)
    windows = engine_diff(scrapes)["batches"]
    if not trace or not trace.get("launches") or n is None or not windows:
        return None
    return trace["busy_s"] / (trace["launches"] * windows / n) * 1e3

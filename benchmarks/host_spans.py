"""The daemon's own host spans beside the device's operations, from one
capture: which part of the device's idle time had no work to give it,
which part fell inside the daemon's housekeeping, and which is left to the
serving path.

From PR 25 on the daemon writes `jax.profiler.TraceAnnotation` spans into
its capture while it runs (obs/profile.py): `front.pull_wait` (a pull-loop
worker blocked waiting for a frame), `lock_wait`, `prep`, `dispatch`,
`readback`, `demux`, `post`, and `bg:<site>` around each background
ticker's unit of work. Host and device planes of one `.xplane.pb` share a
timeline, so no offset is needed. A capture without any of these spans (a
daemon from before that change) gives None.

`split_idle` does the arithmetic on tuples alone, so it can be checked on
a small recorded trace (tests/host_spans_fixture.json.gz). Every instant
in which no operation runs on the device goes to exactly one of:

  housekeeping  a `bg:*` span is open on some thread
  no_work       no `bg:*` span is open, and every pull-loop worker (each
                thread that wrote a `front.pull_wait` span) is inside one
  host          the rest: a serving span is open, or none is

The three are shares of the capture's `window_s` and sum to the trace's
idle share, 1 - busy_s / window_s, as `device_idle_share` computes it:
`host` is that total less the other two, so time the trace does not cover
(the capture's edges) is the host's. Only the first `window_s` of the trace
is looked at: a capture can outlast what was asked (its thread waits for
the GIL behind the very stall it records; 2.55 s for 2 s seen), and what
the device did past that is not in the denominator. `host` then comes out
below zero by the operations that ran past the window, which
`device_idle_share` counted as busy time inside it.
"""

from __future__ import annotations

import functools
import glob
import os
from typing import Dict, List, Optional, Tuple

Span = Tuple[int, str, float, float]  # thread, name, start ns, end ns
Interval = Tuple[float, float]

PULL_WAIT = "front.pull_wait"
BACKGROUND = "bg:"
SERVING = ("lock_wait", "prep", "dispatch", "readback", "demux", "post")

@functools.lru_cache(maxsize=2)  # three readers read one capture
def load(trace_dir: str) -> Tuple[Dict[str, List[Interval]], List[Span]]:
    """({device: its operations' intervals}, the daemon's own host spans)
    of the newest `.xplane.pb` under `trace_dir`. On a trace without a TPU
    plane (a CPU rehearsal) the XLA:CPU thunks of the host plane stand in
    for the device, as in trace_reduce.load_events."""
    import jax

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(sorted(files)[-1])
    ops: Dict[str, List[Interval]] = {}
    stand_in: List[Interval] = []
    spans: List[Span] = []
    thread = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                thread += 1
                xla = "XLA" in line.name or "xla" in line.name
                for e in line.events:
                    if is_ours(e.name):
                        spans.append((thread, e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif xla and not e.name.startswith("$"):
                        stand_in.append((e.start_ns,
                                         e.start_ns + e.duration_ns))
    if not ops and stand_in:
        ops["/host:CPU"] = stand_in
    return ops, spans


def is_ours(name: str) -> bool:
    return name == PULL_WAIT or name in SERVING or name.startswith(BACKGROUND)


def union(intervals) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(a: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, at = [], lo
    for s, e in a:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if s < e]


def length(a: List[Interval]) -> float:
    return sum(e - s for s, e in a)


def all_waiting(spans: List[Span]) -> List[Interval]:
    """Where every pull-loop worker is inside a `front.pull_wait` span."""
    waits = [(t, s, e) for t, name, s, e in spans if name == PULL_WAIT]
    workers = {t for t, _, _ in waits}
    if not workers:
        return []
    edges = sorted([(s, 1) for _, s, _ in waits]
                   + [(e, -1) for _, _, e in waits])
    out, open_, since = [], 0, None
    for at, step in edges:
        open_ += step
        if open_ == len(workers) and since is None:
            since = at
        elif open_ < len(workers) and since is not None:
            if at > since:
                out.append((since, at))
            since = None
    return out


def split_idle(ops: Dict[str, List[Interval]], spans: List[Span],
               busy_s: float, window_s: float) -> Optional[Dict[str, float]]:
    """{"no_work", "housekeeping", "host"}: shares of `window_s`, averaged
    over the devices, summing to 1 - busy_s / window_s. None when the
    trace holds none of the daemon's spans or no device operation."""
    if not spans or not ops or not window_s:
        return None
    lo = min(min(s for _, _, s, _ in spans),
             min(s for dev in ops.values() for s, _ in dev))
    hi = max(max(e for _, _, _, e in spans),
             max(e for dev in ops.values() for _, e in dev))
    hi = min(hi, lo + window_s * 1e9)
    background = union((s, e) for _, name, s, e in spans
                       if name.startswith(BACKGROUND))
    no_work = intersect(all_waiting(spans), complement(background, lo, hi))
    housekeeping_s = no_work_s = 0.0
    for dev in ops.values():
        idle = complement(union(dev), lo, hi)
        housekeeping_s += length(intersect(idle, background)) / 1e9
        no_work_s += length(intersect(idle, no_work)) / 1e9
    housekeeping_s /= len(ops)
    no_work_s /= len(ops)
    return {"no_work": no_work_s / window_s,
            "housekeeping": housekeeping_s / window_s,
            "host": (window_s - busy_s - housekeeping_s - no_work_s)
            / window_s}


def read_share(scrapes: dict, trace: dict, which: str) -> Optional[float]:
    """What the three `idle_share.*` readers return."""
    if not trace or not trace.get("window_s"):
        return None
    capture = scrapes["after"]["profile"].get("capture") or {}
    path = capture.get("last_path")
    if not path or capture.get("last_mode") != "jax_trace":
        return None
    ops, spans = load(path)
    shares = split_idle(ops, spans, trace["busy_s"], trace["window_s"])
    return None if shares is None else shares[which]

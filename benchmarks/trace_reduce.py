"""From a jax.profiler capture to the numbers the device metrics read.

`load_events` turns the capture's `.xplane.pb` into plain tuples;
`reduce_events` does the arithmetic on tuples alone, so it can be checked
on a small recorded trace (tests/trace_fixture.json). What it returns:

  window_s     length of the capture
  busy_s       union of the intervals in which an operation ran on the
               device, averaged over the devices in the trace (a core runs
               one operation at a time, so this is also the summed time of
               the top-level operations)
  launches     program launches per device (events of the `XLA Modules`
               line; on a trace without that line, runs of ops)
  breakdown    {"device_ops": the ten operations that took most time,
                "idle_gaps": the ten longest gaps between operations, named
                by the operation that ended each — what the host was doing
                in a gap needs host spans inside the daemon's trace, which
                it does not write yet}
"""

from __future__ import annotations

import collections
import glob
import os
from typing import Dict, List, Tuple

Event = Tuple[str, str, str, float, float]  # device, line, name, start ns, dur ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_events(trace_dir: str, rehearse: bool = False) -> List[Event]:
    """Events of the device planes (`/device:TPU:n`). A CPU rehearsal has
    no device plane: there the XLA:CPU thunks on the host plane stand in,
    so that the reduction has something to chew on."""
    import jax

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(sorted(files)[-1])
    events: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    events.extend((plane.name, line.name, e.name, e.start_ns,
                                   e.duration_ns) for e in line.events)
        elif rehearse and plane.name == "/host:CPU":
            for line in plane.lines:
                if "XLA" in line.name or "xla" in line.name:
                    events.extend(("/host:CPU", OPS_LINE, e.name, e.start_ns,
                                   e.duration_ns) for e in line.events
                                  if not e.name.startswith("$"))
    return events


def _union_and_gaps(spans):
    """spans: [(start, end, name)] -> (union length, [(gap, name)])."""
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    cur_name = ""
    for s, e, name in sorted(spans):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                busy += cur_e - cur_s
                gaps.append((s - cur_e, cur_name))
            cur_s, cur_e, cur_name = s, e, name
        elif e > cur_e:
            cur_e, cur_name = e, name
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def reduce_events(events: List[Event], window_s: float) -> Dict[str, object]:
    by_dev = collections.defaultdict(lambda: {"ops": [], "modules": []})
    for dev, line, name, start, dur in events:
        by_dev[dev]["modules" if line == MODULES_LINE else "ops"].append(
            (start, start + dur, name))
    n = len(by_dev)
    out = {"window_s": window_s, "devices": n, "busy_s": 0.0, "launches": 0.0,
           "breakdown": {"device_ops": [], "idle_gaps": []}}
    if not n:
        return out
    per_op = collections.Counter()
    gaps_all = []
    for dev, lines in by_dev.items():
        # a traced op can contain child ops (a fusion's steps, a while
        # body): busy time is the union, op time the top-level events
        ops = lines["ops"]
        busy, gaps = _union_and_gaps(ops)
        out["busy_s"] += busy / 1e9 / n
        launches = len(lines["modules"]) or (len(gaps) + 1 if ops else 0)
        out["launches"] += launches / n
        top_end = -1.0
        for s, e, name in sorted(ops):
            if s >= top_end:  # not nested in the previous top-level op
                per_op[name] += (e - s) / 1e9 / n
                top_end = e
        gaps_all.extend((g / 1e9, f"after {name}") for g, name in gaps)
    # the trace names an op by its whole HLO line: the first 120 characters
    # tell them apart
    out["breakdown"]["device_ops"] = [
        [name[:120], sec] for name, sec in per_op.most_common(10)]
    out["breakdown"]["idle_gaps"] = [
        [name[:120], sec] for sec, name in sorted(gaps_all, reverse=True)[:10]]
    return out


def reduce_capture(capture: dict, rehearse: bool = False) -> Dict[str, object]:
    """`capture`: what the daemon's endpoint answered, plus `seconds`."""
    if not capture or not capture.get("ok") or \
            capture.get("mode") != "jax_trace":
        raise RuntimeError(f"the daemon made no jax.profiler trace: {capture}")
    return reduce_events(load_events(capture["path"], rehearse),
                         float(capture["seconds"]))

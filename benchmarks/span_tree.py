"""Every span the daemon writes into a capture, per thread, nested, with
each span's self time: what `host_spans.py`'s three-way split cannot say
about its `host` share, which part of the host the chip was waiting for.

`host_spans.load` keeps the spans its arithmetic is defined on
(`front.pull_wait`, the six serving phases, `bg:*`). From the change that
added this file on, the daemon writes more of them, on every thread that
serves (obs/profile.py, docs/observability.md "Profiling plane"):

  pull                 a pull worker handling one pull (service/peerlink.py):
                       the parent of everything below on that thread, so
                       its SELF time (its duration less what its children
                       cover) is the loop's own Python
  alloc                a staging buffer allocated and zeroed
  stage, launch        inside `dispatch`: the host's work on the bytes
                       before the jitted call, and the call itself
                       (launches are serialised under the engine lock, so
                       the n-th `launch` span of a capture is the n-th
                       event of the device's `XLA Modules` line)
  device_wait, fetch   inside `readback`: block_until_ready, and the copy
                       back with its widening
  leftover             with .build / .serve / .fill inside it
  combiner.wait, .form the combiner's two threads, blocked and forming

A span's parent is the innermost span of its thread that contains it: the
capture nests by time, so nothing else is needed. `union`, `intersect`,
`complement` and `length` are host_spans', by import.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterator, List, Optional, Tuple

import host_spans
from host_spans import Span, intersect, length, union

NAMES = frozenset(
    host_spans.SERVING
    + (host_spans.PULL_WAIT, "pull", "alloc", "stage", "launch",
       "device_wait", "fetch", "leftover", "leftover.build",
       "leftover.serve", "leftover.fill", "combiner.wait", "combiner.form"))


def is_ours(name: str) -> bool:
    return name in NAMES or name.startswith(host_spans.BACKGROUND)


class Node:
    """One span with the spans nested in it on its thread."""

    __slots__ = ("thread", "name", "start", "end", "children")

    def __init__(self, span: Span):
        self.thread, self.name, self.start, self.end = span
        self.children: List["Node"] = []

    @property
    def duration(self) -> float:
        return self.end - self.start

    @property
    def self_time(self) -> float:
        """The span's duration less the part its children cover."""
        covered = union((max(c.start, self.start), min(c.end, self.end))
                        for c in self.children)
        return self.duration - length(covered)


def load(trace_dir: str) -> List[Span]:
    """The daemon's spans on the host plane of the newest `.xplane.pb`
    under `trace_dir`, one thread index a line (as host_spans.load counts
    them)."""
    import jax

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(sorted(files)[-1])
    spans: List[Span] = []
    thread = 0
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            thread += 1
            for e in line.events:
                if is_ours(e.name):
                    spans.append((thread, e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
    return spans


def forest(spans: List[Span]) -> Dict[int, List[Node]]:
    """{thread: its outermost spans in time order}, every other span
    hanging from the innermost one that contains it."""
    roots: Dict[int, List[Node]] = {}
    open_: Dict[int, List[Node]] = {}
    # a parent sorts before its children: earlier start, or the same
    # start and a later end
    for span in sorted(spans, key=lambda s: (s[0], s[2], -s[3])):
        node = Node(span)
        stack = open_.setdefault(node.thread, [])
        while stack and stack[-1].end < node.end:
            stack.pop()
        if stack:
            stack[-1].children.append(node)
        else:
            roots.setdefault(node.thread, []).append(node)
        stack.append(node)
    return roots


def walk(nodes: List[Node]) -> Iterator[Node]:
    for node in nodes:
        yield node
        yield from walk(node.children)


def named(trees: Dict[int, List[Node]], name: str) -> List[Node]:
    return [n for roots in trees.values() for n in walk(roots)
            if n.name == name]


def self_time_mean_ms(trees: Dict[int, List[Node]],
                      name: str) -> Optional[float]:
    """Mean self time of the spans called `name`, ms; None without one."""
    nodes = named(trees, name)
    if not nodes:
        return None
    return sum(n.self_time for n in nodes) / len(nodes) / 1e6


def threads_with(trees: Dict[int, List[Node]], name: str) -> List[int]:
    """The threads that wrote a span called `name`."""
    return sorted({n.thread for n in named(trees, name)})


def coverage(roots: List[Node], lo: float, hi: float,
             names=None) -> float:
    """Share of [lo, hi] that one thread's spans cover: all of them, or
    those called one of `names` (wherever they are nested)."""
    picked = roots if names is None else \
        [n for n in walk(roots) if n.name in names]
    covered = intersect(union((n.start, n.end) for n in picked), [(lo, hi)])
    return length(covered) / (hi - lo) if hi > lo else 0.0


def bounds(spans: List[Span]) -> Tuple[float, float]:
    """First start and last end of the daemon's spans: the capture, as
    far as the host plane shows it."""
    return min(s[2] for s in spans), max(s[3] for s in spans)


def capture_path(scrapes: dict, trace: dict) -> Optional[str]:
    """Where the daemon wrote the capture that `trace` was reduced from;
    None where there is none (an untraced run, a wall-sampler capture)."""
    if not trace or not trace.get("window_s"):
        return None
    capture = scrapes["after"]["profile"].get("capture") or {}
    if capture.get("last_mode") != "jax_trace":
        return None
    return capture.get("last_path")


def report(trace_dir: str) -> str:
    """One line a thread and span name of a capture: count, total and self
    time in ms, and the share of the capture the thread's spans cover
    (`python3 benchmarks/span_tree.py <capture dir>`)."""
    spans = load(trace_dir)
    if not spans:
        return "none of the daemon's spans in this capture\n"
    lo, hi = bounds(spans)
    lines = [f"capture {(hi - lo) / 1e6:.1f} ms by the daemon's spans"]
    for thread, roots in sorted(forest(spans).items()):
        lines.append(f"thread {thread}: covered "
                     f"{coverage(roots, lo, hi):.3f}")
        by_name: Dict[str, List[Node]] = {}
        for node in walk(roots):
            by_name.setdefault(node.name, []).append(node)
        for name, nodes in sorted(by_name.items()):
            lines.append(
                f"  {name:<18} n {len(nodes):>6}  total "
                f"{sum(n.duration for n in nodes) / 1e6:>10.3f}  self "
                f"{sum(n.self_time for n in nodes) / 1e6:>10.3f}")
    return "\n".join(lines) + "\n"


if __name__ == "__main__":
    import sys

    sys.stdout.write(report(sys.argv[1]))

"""What held the machine while a window ran: the load generators, the daemon
and this parent share one host, and a stall of seconds that holds them all
(PERF.md section 6, PR 45) reads in a call's latency like a stall of the
daemon. Three witnesses, each read where the kernel offers it and left out
where it does not:

  counters()  pressure-stall totals of /proc/pressure/{cpu,memory,io}, the
              cgroup's CPU throttling (`cpu.stat`), and the steal and iowait
              columns of /proc/stat; diffed across the window by `diff()`
  Watch       a thread of this process that sleeps 10 ms at a time and keeps
              every wake-up that came 20 ms or more late: a gap here, at the
              instant the generators ran late too, is the host's and not the
              daemon's

Imports nothing of the program and no JAX."""

from __future__ import annotations

import os
import threading
import time

TICK_S, GAP_S = 0.010, 0.020


def _read(path: str):
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def _cgroup_cpu_stat():
    """The text of this process's cgroup's `cpu.stat` (v2, then v1)."""
    rel = ""
    for ln in (_read("/proc/self/cgroup") or "").splitlines():
        hierarchy, controllers, path = ln.split(":", 2)
        if hierarchy == "0" or "cpu" in controllers.split(","):
            rel = path.strip("/")
            break
    for root in ("/sys/fs/cgroup", "/sys/fs/cgroup/cpu",
                 "/sys/fs/cgroup/cpu,cpuacct"):
        for path in (os.path.join(root, rel, "cpu.stat"),
                     os.path.join(root, "cpu.stat")):
            text = _read(path)
            if text and "nr_throttled" in text:
                return text
    return None


def counters() -> dict:
    """Cumulative counters, ms or counts; a key is there only where the
    kernel has the file."""
    out = {}
    for what in ("cpu", "memory", "io"):
        for ln in (_read(f"/proc/pressure/{what}") or "").splitlines():
            kind, *fields = ln.split()
            total = dict(f.split("=") for f in fields).get("total")
            if total is not None:  # microseconds
                out[f"psi_{what}_{kind}_ms"] = int(total) / 1e3
    stat = dict(ln.split()[:2] for ln in (_cgroup_cpu_stat() or "").splitlines()
                if len(ln.split()) >= 2)
    for key, name, scale in (("nr_periods", "cgroup_periods", 1),
                             ("nr_throttled", "cgroup_throttled", 1),
                             ("throttled_usec", "cgroup_throttled_ms", 1e-3),
                             ("throttled_time", "cgroup_throttled_ms", 1e-6)):
        if key in stat:
            out[name] = int(stat[key]) * scale
    first = (_read("/proc/stat") or "").splitlines()[:1]
    if first and first[0].startswith("cpu "):
        ticks = [int(x) for x in first[0].split()[1:]]
        ms = 1e3 / os.sysconf("SC_CLK_TCK")
        if len(ticks) >= 8:  # user nice system idle iowait irq softirq steal
            out["stat_iowait_ms"] = ticks[4] * ms
            out["stat_steal_ms"] = ticks[7] * ms
    return out


def diff(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after if k in before}


class Watch:
    """Wake-ups of a sleeping thread that came late, as (seconds after
    `origin`, ms late), `origin` a `time.time()` instant."""

    def __init__(self, origin: float):
        self.origin = origin
        self.gaps = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        last = time.perf_counter()
        while not self._stop.wait(TICK_S):
            now = time.perf_counter()
            late = now - last - TICK_S
            if late >= GAP_S:
                self.gaps.append((time.time() - self.origin - late, late * 1e3))
            last = now

    def stop(self, first_s: float, last_s: float) -> dict:
        """The gaps that began in [first_s, last_s) after the origin: how
        many, their sum, and the five longest."""
        self._stop.set()
        self._thread.join()
        gaps = [(at, ms) for at, ms in self.gaps if first_s <= at < last_s]
        longest = sorted(gaps, key=lambda g: -g[1])[:5]
        return {"gaps": len(gaps), "gap_sum_ms": sum(ms for _, ms in gaps),
                "gap_max_ms": max((ms for _, ms in gaps), default=0.0),
                "longest": [[round(at, 3), round(ms, 1)] for at, ms in longest]}

"""Continuous profiling plane: live serving-cycle decomposition.

The profiler makes every node measure its own serving cycle
continuously: monotonic stamps at the serving path's real seams —
combiner queue wait, engine-lock acquire wait, host prep, device
dispatch, readback wait, response demux — feed streaming log2
histograms per phase, cheap enough to stay on in production (PERF.md
§6: tracing moved no end-to-end metric out of its spread on the chip).

Consumers:

- /v1/debug/profile (service/http_gateway.py): full per-phase
  histograms, per-call-site lock-wait accounting, the live
  decomposition, and the on-demand deep-capture trigger;
- /v1/debug/vars "profile" section (obs/introspect.py): the compact
  always-on summary;
- profile_* columns in the metrics-history ring (obs/history.py), so
  decomposition drift is visible over the retention window and the
  anomaly engine's `profile_shift` detector can compare fast/slow
  windows;
- the benchmark's per-layer readers (benchmarks/layer_metrics/), which
  diff the same phase totals over a measured window.

Inside the cycle, the engines' launch and fetch funnels split `dispatch`
and `readback` where the bytes are staged and waited for (SUB_PHASES:
`stage` and `launch` every launch; `device_wait` and `fetch` while a
capture runs, because telling them apart takes a wait of its own), and
every release of the engine lock on a serving path books how long it was
HELD (`lock_hold(site, ns)`) beside how long it was waited for. Both
stand outside PHASES and the decomposition, so nothing that reads those
moves.

Beside the serving cycle it meters what surrounds it, on the same
clock (CLOCK_MONOTONIC): the native front's own histograms (frame
residency before the pull, whole-call residency, parse and write time —
native/peerlink.cpp, read at scrape time through `attach_front`) and the
background tickers' units of work (`background(site)`: the anomaly
sweep, the ledger audit, history samples, keyspace harvests), so "what
stopped serving for a second" has an answer inside the daemon. While a
deep capture runs the same seams write `jax.profiler.TraceAnnotation`
spans into the capture, named as the phases are (the sub-phases nested
in theirs, the combiner's threads as `combiner.wait` / `combiner.form`,
a pull's handling as `pull`): host and device share one timeline there,
so a device idle gap can be put down to what the host was doing.

`GUBER_PROFILE=0` turns every observation site into a single attribute
test (a site that reads a clock for the profiler alone tests `enabled`
first and skips the read); the off path is bit-identical
(differential-tested) because the profiler only ever *reads* clocks.
"""

from __future__ import annotations

import collections
import contextlib
import hashlib
import json
import os
import sys
import threading
import time
from typing import Dict, Optional, Tuple

from gubernator_tpu.obs import witness

# v2: phases gains front_wait/front_call/front_parse/front_write (the
# native front's histograms) and the body gains `front` (its counters),
# `bg_sites` (background tickers per site) and capture.options.
# v3: phases gains `leftover`; capture.last_rates gains the front's
# counters across the capture (frames_pulled_in, items_pulled_in).
# v4: phases gains the four SUB_PHASES and `lock_hold`; the body gains
# `lock_hold_sites`, shaped as `lock_sites` is.
PROFILE_SCHEMA_VERSION = 4
# kernels v2: `lanes_total` is gone (no dispatch ever fed it)
KERNELS_SCHEMA_VERSION = 2

# The serving-cycle phases, in cycle order. queue_wait overlaps the
# serial phases of OTHER windows, so decomposition shares are computed
# over the serial set only; queue_wait's "share" is reported against the
# same denominator as a residency ratio (can exceed 1 under deep
# pipelining). Its feed differs by path: behind the Python combiner it is
# a ticket's residency before launch; behind the native front
# (service/peerlink.py columnar path) it is ONLY the drain that a full
# pipeline forces before the next launch — the queue there is the C++
# frame queue, metered as front_wait.
PHASES = ("queue_wait", "lock_wait", "prep", "dispatch", "readback", "demux")
SERIAL_PHASES = ("lock_wait", "prep", "dispatch", "readback", "demux")
# The native front's own histograms (native/peerlink.cpp pls_profile), per
# frame: parsed -> pulled, parsed -> reply written, wire bytes -> columns,
# reply serialise + send. Outside the serial cycle: a frame waits while
# other windows run.
FRONT_PHASES = ("front_wait", "front_call", "front_parse", "front_write")
# What `dispatch` and `readback` are made of, stamped inside the funnels
# every launch and every fetch goes through (models/engine.py _launch /
# _fetch_staged, parallel/sharded.py _launch_mesh / _fetch_mesh). `stage`
# (funnel entry -> the jitted call: the hot tracker's feed, lean_window /
# compact_window and their refusals) and `launch` (the jitted call:
# enqueue and host -> device placement) lie inside `dispatch`, one
# observation a launch. `device_wait` (block_until_ready on the answer)
# and `fetch` (the copy back and its widening) lie inside `readback`, one
# observation a launch FETCHED WHILE A CAPTURE RUNS: the copy waits for
# the device by itself, so telling the two apart takes an explicit wait,
# a second release of the GIL a window, which a daemon nobody is looking
# into does not pay. Outside PHASES, the decomposition and the history
# ring's columns, as `leftover` is.
SUB_PHASES = ("stage", "launch", "device_wait", "fetch")
FRONT_COUNTERS = ("pulls", "frames_pulled", "items_pulled", "frames_native")

# a background unit of work longer than this lands in the flight recorder
BACKGROUND_SLOW_NS = 100_000_000

# what capture() hands jax.profiler: no Python tracer (it slowed the
# daemon to a third of its launch rate while a capture lasted), host
# tracer at the level TraceAnnotation records at
CAPTURE_OPTIONS = {"python_tracer_level": 0, "host_tracer_level": 2}

# log2-ns histogram: bucket i holds observations <= 2^(i+_SHIFT) ns.
# _SHIFT=10 puts bucket 0 at ~1 us (finer resolution is clock noise on
# these seams); 28 buckets reach ~137 s.
_SHIFT = 10
_NBUCKETS = 28
# pls_profile's layout: per front phase its bucket counts, then n,
# total_ns, max_ns; then the counters
_FRONT_HIST_LEN = _NBUCKETS + 3
FRONT_PROFILE_LEN = len(FRONT_PHASES) * _FRONT_HIST_LEN + len(FRONT_COUNTERS)


def _bucket_quantile(counts, n: int, q: float) -> int:
    """Upper bucket bound holding quantile `q` of a log2-ns histogram
    (0 when empty)."""
    if n == 0:
        return 0
    want = q * n
    seen = 0
    for i, c in enumerate(counts):
        seen += c
        if seen >= want:
            return 1 << (i + _SHIFT)
    return 1 << (_NBUCKETS - 1 + _SHIFT)


def _hist_snapshot(counts, n: int, total_ns: int, max_ns: int) -> dict:
    return {
        "n": n,
        "total_ns": total_ns,
        "max_ns": max_ns,
        "p50_ns": _bucket_quantile(counts, n, 0.50),
        "p99_ns": _bucket_quantile(counts, n, 0.99),
    }


def _annotation(name: str):
    """An entered jax.profiler.TraceAnnotation: a host span in the running
    capture. (Entered outside a capture it records nothing.)"""
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(name)
    ann.__enter__()
    return ann


class _Seams:
    """One call's chain of host spans in a capture: `seams(name)` closes
    the open span and opens the next, `seams(None)` closes the last. Lives
    as long as the call that asked for it, so nothing is left open. A
    funnel's own chain (`stage`, `launch`; `device_wait`, `fetch`) runs
    while its caller's `dispatch` or `readback` span is open: the capture
    nests spans by time."""

    __slots__ = ("_ann",)

    def __init__(self):
        self._ann = None

    def __call__(self, name: Optional[str]) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._ann = None if name is None else _annotation(name)


def _no_seams(name: Optional[str]) -> None:
    """What Profiler.seams() hands out while no capture runs."""


_NO_SPAN = contextlib.nullcontext()


def background_of(holder, site: str):
    """`with background_of(x, site):` — Profiler.background on
    `x.profiler`; nothing when x carries no profiler (stubs, None)."""
    prof = getattr(holder, "profiler", None)
    return _NO_SPAN if prof is None else prof.background(site)


def seams_of(holder):
    """`holder.profiler.seams()`; the chain that does nothing when
    `holder` carries no profiler (stubs, None)."""
    prof = getattr(holder, "profiler", None)
    return _no_seams if prof is None else prof.seams()


def profile_enabled_default() -> bool:
    """GUBER_PROFILE escape hatch (Go ParseBool values; default on — the
    profiler is the always-on cycle meter, opting OUT is the deliberate
    act)."""
    raw = os.environ.get("GUBER_PROFILE", "").strip().lower()
    if raw in ("0", "f", "false", "no", "off"):
        return False
    return True


class PhaseHist:
    """One streaming log2-ns histogram: O(1) observe under a lock, exact
    count/total/max, bucket-resolution quantiles."""

    __slots__ = ("_lock", "counts", "n", "total_ns", "max_ns")

    def __init__(self):
        self._lock = witness.make_lock("profiler.hist")
        self.counts = [0] * _NBUCKETS
        self.n = 0
        self.total_ns = 0
        self.max_ns = 0

    def observe(self, ns: int) -> None:
        if ns < 0:
            ns = 0
        idx = ns.bit_length() - _SHIFT
        if idx < 0:
            idx = 0
        elif idx >= _NBUCKETS:
            idx = _NBUCKETS - 1
        with self._lock:
            self.counts[idx] += 1
            self.n += 1
            self.total_ns += ns
            if ns > self.max_ns:
                self.max_ns = ns

    def totals(self) -> Tuple[int, int]:
        with self._lock:
            return self.n, self.total_ns

    def snapshot(self) -> dict:
        with self._lock:
            return _hist_snapshot(self.counts, self.n, self.total_ns,
                                  self.max_ns)


class Profiler:
    """The per-engine cycle profiler: phase histograms, per-call-site
    lock-wait accounting, a snapshot ring for windowed views, and the
    rate-limited deep capture."""

    def __init__(self, enabled: Optional[bool] = None,
                 capture_min_interval_s: float = 60.0):
        self.enabled = (profile_enabled_default()
                        if enabled is None else bool(enabled))
        self.capture_min_interval_s = float(capture_min_interval_s)
        self._phases: Dict[str, PhaseHist] = {p: PhaseHist() for p in PHASES}
        # a pull worker's stretch inside service/peerlink.py
        # _leftover_items, one observation a columnar chunk that handed
        # back leftovers (the request objects built, the router, the
        # combiner, the engine's rounds, the fill of the answer rows). It
        # holds whole serving cycles of other threads, so it stands
        # outside PHASES and the decomposition, as the front's phases do
        self._leftover = PhaseHist()
        self._sub: Dict[str, PhaseHist] = {p: PhaseHist() for p in SUB_PHASES}
        # how long the engine lock was HELD on a serving path, beside how
        # long it was waited for: total and per site, as lock_wait is
        self._lock_hold = PhaseHist()
        self._hold_sites: Dict[str, PhaseHist] = {}
        self._sites: Dict[str, PhaseHist] = {}
        self._bg_sites: Dict[str, PhaseHist] = {}
        self._sites_lock = witness.make_lock("profiler.sites")
        # the native front's reader (service/peerlink.py front_profile),
        # called at scrape time only
        self._front = None
        # flight recorder for slow background units (Instance wires it)
        self.recorder = None
        # background units in progress: unit id -> [site, open capture
        # span or None]. capture() opens a span for the units it finds
        # running and closes the ones still running when it ends, so a
        # unit longer than the capture is in it whole.
        self._bg_lock = witness.make_lock("profiler.background")
        self._bg_open: Dict[int, list] = {}
        self._bg_next = 0
        self._bg_local = threading.local()  # per thread: nested units' ns
        # true while a jax.profiler capture runs: the seams then write
        # their spans into it (one attribute test each when it is not)
        self._capturing = False
        # windowed views (slow-request attachment, anomaly baselines that
        # predate the history ring): totals snapshots every ~2 s, taken
        # lazily from the observe path so idle engines cost nothing
        self._ring: "collections.deque[tuple]" = collections.deque(maxlen=128)
        self._ring_tick_s = 2.0
        self._ring_last = 0.0
        self._obs_since_tick = 0
        # deep capture state
        self._capture_lock = witness.make_lock("profiler.capture")
        self._last_capture = 0.0
        self._captures = 0
        self._last_capture_path: Optional[str] = None
        self._last_capture_mode: Optional[str] = None
        self._last_capture_rates: Optional[dict] = None

    # ------------------------------------------------------- observation

    def observe(self, phase: str, ns: int) -> None:
        """Record `ns` nanoseconds spent in `phase` for one window."""
        if not self.enabled:
            return
        self._phases[phase].observe(ns)
        self._obs_since_tick += 1
        if self._obs_since_tick >= 256:
            self._maybe_tick()

    def lock_wait(self, site: str, ns: int) -> None:
        """Record one engine-lock acquisition wait at `site` (feeds both
        the lock_wait phase and the per-site histogram)."""
        if not self.enabled:
            return
        self._phases["lock_wait"].observe(ns)
        self._site(self._sites, site).observe(ns)

    def lock_hold(self, site: str, ns: int) -> None:
        """Record one hold of the engine lock at `site`, acquire to
        release (the `lock_hold` phase and the per-site histogram)."""
        if not self.enabled:
            return
        self._lock_hold.observe(ns)
        self._site(self._hold_sites, site).observe(ns)

    def _site(self, hists: Dict[str, PhaseHist], site: str) -> PhaseHist:
        h = hists.get(site)
        if h is None:
            with self._sites_lock:
                h = hists.setdefault(site, PhaseHist())
        return h

    def observe_sub(self, phase: str, ns: int) -> None:
        """Record `ns` nanoseconds of one launch (or of one fetch) in a
        SUB_PHASES member."""
        if self.enabled:
            self._sub[phase].observe(ns)

    @contextlib.contextmanager
    def background(self, site: str):
        """Time one unit of a background ticker's work (the anomaly
        sweep, the ledger audit, a history sample, a keyspace harvest...)
        into the per-site histograms `bg_sites`, as lock_wait feeds
        `lock_sites`. These threads take the GIL and the engine lock from
        the serving threads, so a long unit IS a serving stall: one over
        100 ms also lands in the flight recorder, and while a capture runs
        the unit is a `bg:<site>` span in it.

        Units nest (the anomaly sweep runs the ledger audit, which
        resolves slots): a site's histogram holds its OWN time, the unit
        less the units nested in it on the same thread, so the sites add
        up to the time the tickers took. The recorder event and the
        capture span carry the whole unit."""
        if not self.enabled:
            yield
            return
        nested = getattr(self._bg_local, "nested_ns", None)
        if nested is None:
            nested = self._bg_local.nested_ns = []
        nested.append(0)
        with self._bg_lock:
            unit = self._bg_next = self._bg_next + 1
            self._bg_open[unit] = [
                site, _annotation("bg:" + site) if self._capturing else None]
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            ns = time.perf_counter_ns() - t0
            with self._bg_lock:
                ann = self._bg_open.pop(unit)[1]
                if ann is not None:
                    ann.__exit__(None, None, None)
            own_ns = ns - nested.pop()
            if nested:
                nested[-1] += ns
            self._site(self._bg_sites, site).observe(own_ns)
            rec = self.recorder
            if ns >= BACKGROUND_SLOW_NS and rec is not None:
                rec.emit("profile.background_slow", site=site,
                         ms=round(ns / 1e6, 1), own_ms=round(own_ns / 1e6, 1))

    def observe_leftover(self, ns: int) -> None:
        """Record one chunk's stretch inside _leftover_items (the
        `leftover` phase of /v1/debug/profile)."""
        if self.enabled:
            self._leftover.observe(ns)

    @property
    def capturing(self) -> bool:
        """True while a jax.profiler capture runs: what the fetch funnels
        test before they wait for the device apart from the copy."""
        return self._capturing

    def seams(self):
        """`seams = prof.seams()`, then `seams("prep")` ... `seams(None)`
        at a call's stamps: a chain of host spans while a capture runs, a
        function that does nothing while none does."""
        return _Seams() if self._capturing else _no_seams

    def span(self, name: str):
        """`with prof.span(name):` — a host span in the running capture,
        nothing outside one."""
        if not self._capturing:
            return _NO_SPAN
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(name)

    def attach_front(self, reader) -> None:
        """`reader()` -> the FRONT_PROFILE_LEN numbers pls_profile wrote
        (native/peerlink.cpp), or None when the front is gone; None
        detaches."""
        self._front = reader

    def front_totals(self) -> Tuple[Dict[str, dict], Dict[str, int]]:
        """({front phase: snapshot}, {front counter: value}); zeros when
        no native front is attached (grpcio deployments, library use)."""
        reader = self._front
        vals = reader() if reader is not None else None
        if vals is None:
            vals = [0] * FRONT_PROFILE_LEN
        phases = {}
        for i, p in enumerate(FRONT_PHASES):
            h = vals[i * _FRONT_HIST_LEN:(i + 1) * _FRONT_HIST_LEN]
            phases[p] = _hist_snapshot(h[:_NBUCKETS], *h[_NBUCKETS:])
        return phases, dict(zip(FRONT_COUNTERS,
                                vals[-len(FRONT_COUNTERS):]))

    def _maybe_tick(self) -> None:
        self._obs_since_tick = 0
        now = time.monotonic()
        if now - self._ring_last < self._ring_tick_s:
            return
        self._ring_last = now
        self._ring.append((now, self.totals()))

    # ------------------------------------------------------------- views

    def totals(self) -> Dict[str, dict]:
        """Cumulative per-phase counters: {phase: {"n", "total_ns"}}.
        Cheap — the delta source for history columns, bench, slow logs."""
        out = {}
        for p, h in self._phases.items():
            n, total = h.totals()
            out[p] = {"n": n, "total_ns": total}
        return out

    def site_totals(self) -> Dict[str, dict]:
        return self._totals_of(self._sites)

    def background_totals(self) -> Dict[str, dict]:
        return self._totals_of(self._bg_sites)

    def _totals_of(self, hists: Dict[str, PhaseHist]) -> Dict[str, dict]:
        with self._sites_lock:
            sites = dict(hists)
        out = {}
        for s, h in sites.items():
            n, total = h.totals()
            out[s] = {"n": n, "total_ns": total}
        return out

    def recent(self, window_s: float = 60.0) -> dict:
        """Per-phase decomposition over roughly the last `window_s`
        seconds (snapshot-ring resolution ~2 s). The slow-request log
        attaches this so a slow request shows where its window's time
        went without a separate capture."""
        cur = self.totals()
        now = time.monotonic()
        base = None
        base_t = None
        for t, snap in self._ring:
            if now - t <= window_s:
                base = snap
                base_t = t
                break
        if base is None:
            base = {p: {"n": 0, "total_ns": 0} for p in PHASES}
            base_t = None
        phases = {}
        for p in PHASES:
            phases[p] = {
                "n": cur[p]["n"] - base[p]["n"],
                "total_ns": cur[p]["total_ns"] - base[p]["total_ns"],
            }
        serial = sum(phases[p]["total_ns"] for p in SERIAL_PHASES)
        for p in PHASES:
            phases[p]["share"] = (
                round(phases[p]["total_ns"] / serial, 4) if serial else 0.0)
        return {
            "window_s": round(now - base_t, 1) if base_t else None,
            "phases": phases,
        }

    def decomposition(self) -> dict:
        """The live cycle decomposition from boot-cumulative totals:
        per-phase total seconds, window count, mean, and share of the
        serial cycle (see PHASES for the queue_wait caveat)."""
        cur = self.totals()
        serial = sum(cur[p]["total_ns"] for p in SERIAL_PHASES)
        out = {}
        for p in PHASES:
            n = cur[p]["n"]
            total = cur[p]["total_ns"]
            out[p] = {
                "count": n,
                "total_s": round(total / 1e9, 6),
                "avg_us": round(total / n / 1e3, 3) if n else 0.0,
                "share": round(total / serial, 4) if serial else 0.0,
            }
        return out

    def debug(self) -> dict:
        """The /v1/debug/vars "profile" section: compact summary."""
        cur = self.totals()
        serial = sum(cur[p]["total_ns"] for p in SERIAL_PHASES)
        return {
            "enabled": self.enabled,
            "phases": {p: {"n": cur[p]["n"],
                           "total_s": round(cur[p]["total_ns"] / 1e9, 3)}
                       for p in PHASES},
            "shares": {p: (round(cur[p]["total_ns"] / serial, 4)
                           if serial else 0.0) for p in SERIAL_PHASES},
            "lock_sites": len(self._sites),
            "bg_sites": len(self._bg_sites),
            "captures": self._captures,
        }

    def endpoint_body(self) -> dict:
        """The schema-pinned /v1/debug/profile body
        (tests/test_debug_schema.py)."""
        with self._sites_lock:
            sites = dict(self._sites)
            holds = dict(self._hold_sites)
            bg = dict(self._bg_sites)
        front_phases, front_counters = self.front_totals()
        phases = {p: h.snapshot() for p, h in self._phases.items()}
        phases.update(front_phases)
        phases["leftover"] = self._leftover.snapshot()
        phases.update((p, h.snapshot()) for p, h in self._sub.items())
        phases["lock_hold"] = self._lock_hold.snapshot()
        return {
            "schema_version": PROFILE_SCHEMA_VERSION,
            "enabled": self.enabled,
            "phases": phases,
            "front": {"attached": self._front is not None,
                      **front_counters},
            "lock_sites": {s: h.snapshot() for s, h in sorted(sites.items())},
            "lock_hold_sites": {s: h.snapshot()
                                for s, h in sorted(holds.items())},
            "bg_sites": {s: h.snapshot() for s, h in sorted(bg.items())},
            "decomposition": self.decomposition(),
            "recent": self.recent(),
            "capture": {
                "count": self._captures,
                "min_interval_s": self.capture_min_interval_s,
                "last_path": self._last_capture_path,
                "last_mode": self._last_capture_mode,
                "last_rates": self._last_capture_rates,
                "options": dict(CAPTURE_OPTIONS),
            },
        }

    # ------------------------------------------------------ deep capture

    def capture(self, out_dir: str, seconds: float = 0.25,
                mode: str = "auto") -> dict:
        """On-demand deep capture, rate-limited to one per
        `capture_min_interval_s`. `mode` "auto" tries `jax.profiler`
        (device timeline, the program's own host spans beside it, no
        Python tracer: CAPTURE_OPTIONS) and falls back to the wall-clock
        stack sampler; "wall" forces the sampler. Writes under `out_dir`
        (the bundle dir) and returns {"ok", "path"/"error", "mode"}, and
        for a jax trace what the capture cost the daemon:
        `launches_per_s_in` (device launches per second while it ran) and
        `launches_per_s_out` (over the `seconds`, at most 2, before it),
        and `frames_pulled_in` / `items_pulled_in` (calls and items the
        front's pull loop took between the capture's two edges);
        never raises."""
        now = time.monotonic()
        with self._capture_lock:
            since = now - self._last_capture
            if self._captures and since < self.capture_min_interval_s:
                return {"ok": False, "error": "rate_limited",
                        "retry_in_s": round(
                            self.capture_min_interval_s - since, 1)}
            self._last_capture = now
            self._captures += 1
        seconds = min(max(float(seconds), 0.05), 10.0)
        stamp = int(time.time())
        try:
            os.makedirs(out_dir, exist_ok=True)
        except OSError as e:
            return {"ok": False, "error": f"capture dir: {e}"}
        if mode == "auto":
            try:
                path = os.path.join(out_dir, f"profile_trace_{stamp}")
                rates = self._jax_trace(path, seconds)
                self._last_capture_path = path
                self._last_capture_mode = "jax_trace"
                self._last_capture_rates = rates
                return {"ok": True, "path": path, "mode": "jax_trace",
                        **rates}
            except Exception:  # noqa: BLE001 — fall through to the sampler
                pass
        try:
            path = self._wall_sample(out_dir, seconds, stamp)
        except Exception as e:  # noqa: BLE001 — capture must not raise
            return {"ok": False, "error": str(e)}
        self._last_capture_path = path
        self._last_capture_mode = "wall_sampler"
        return {"ok": True, "path": path, "mode": "wall_sampler"}

    def _edge(self) -> Tuple[int, int, int, int]:
        """(device launches, frames pulled, items pulled so far, now):
        every launch observes the dispatch phase once; the front counts
        the calls and the items its pull loop took."""
        front = self.front_totals()[1]
        return (self._phases["dispatch"].totals()[0],
                front["frames_pulled"], front["items_pulled"],
                time.perf_counter_ns())

    def _jax_trace(self, path: str, seconds: float) -> dict:
        """One jax.profiler trace of `seconds`, with the seams' spans in
        it; returns the launch rates before and inside it, and what the
        front's pull loop took between the capture's two edges (the work
        its device time belongs to)."""
        import jax

        options = jax.profiler.ProfileOptions()
        for k, v in CAPTURE_OPTIONS.items():
            setattr(options, k, v)
        n0, _, _, t0 = self._edge()
        time.sleep(min(seconds, 2.0))
        n1, _, _, t1 = self._edge()
        jax.profiler.start_trace(path, profiler_options=options)
        try:
            with self._bg_lock:
                # units already running: their spans start here
                for entry in self._bg_open.values():
                    entry[1] = _annotation("bg:" + entry[0])
                self._capturing = True
            n2, f2, i2, t2 = self._edge()
            time.sleep(seconds)
            n3, f3, i3, t3 = self._edge()
        finally:
            with self._bg_lock:
                self._capturing = False
                # units still running: their spans end here
                for entry in self._bg_open.values():
                    if entry[1] is not None:
                        entry[1].__exit__(None, None, None)
                        entry[1] = None
            jax.profiler.stop_trace()
        return {"launches_per_s_out": (n1 - n0) / ((t1 - t0) / 1e9),
                "launches_per_s_in": (n3 - n2) / ((t3 - t2) / 1e9),
                "frames_pulled_in": f3 - f2, "items_pulled_in": i3 - i2}

    @staticmethod
    def _wall_sample(out_dir: str, seconds: float, stamp: int) -> str:
        """Wall-clock stack sampler: collapse every thread's stack every
        ~5 ms into flamegraph-style "frame;frame;frame" counts."""
        interval = 0.005
        stacks: Dict[str, int] = {}
        samples = 0
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline:
            for frames in sys._current_frames().values():  # noqa: SLF001
                parts = []
                f = frames
                depth = 0
                while f is not None and depth < 48:
                    code = f.f_code
                    parts.append(f"{os.path.basename(code.co_filename)}:"
                                 f"{code.co_name}")
                    f = f.f_back
                    depth += 1
                key = ";".join(reversed(parts))
                stacks[key] = stacks.get(key, 0) + 1
            samples += 1
            time.sleep(interval)
        top = sorted(stacks.items(), key=lambda kv: -kv[1])[:200]
        path = os.path.join(out_dir, f"profile_sample_{stamp}.json")
        with open(path, "w", encoding="utf-8") as fh:
            json.dump({"mode": "wall_sampler", "seconds": seconds,
                       "interval_s": interval, "samples": samples,
                       "stacks": dict(top)}, fh, indent=1)
        return path


def check_recompile(fingerprints: Dict[str, str], state_path: str,
                    recorder=None) -> dict:
    """Compare this boot's kernel HLO fingerprints against the previous
    boot's (persisted at `state_path` under the bundle dir) and persist
    the new set. A changed fingerprint means XLA will compile a
    DIFFERENT program for the same serving shape than last boot — a
    jax/libtpu bump, a kernel edit, a flag drift — exactly the moment a
    perf cliff sneaks in, so it lands in the flight recorder as
    `profile.recompile`. Returns {"changed": {...}, "first_boot": bool};
    never raises."""
    prev: Dict[str, str] = {}
    first_boot = True
    try:
        with open(state_path, encoding="utf-8") as fh:
            prev = json.load(fh)
        first_boot = False
    except (OSError, ValueError):
        prev = {}
    changed = {k: {"was": prev[k], "now": v}
               for k, v in fingerprints.items()
               if k in prev and prev[k] != v}
    try:
        os.makedirs(os.path.dirname(state_path) or ".", exist_ok=True)
        with open(state_path, "w", encoding="utf-8") as fh:
            json.dump({**prev, **fingerprints}, fh, indent=1)
    except OSError:
        pass
    if changed and recorder is not None:
        try:
            recorder.emit("profile.recompile",
                          kernels=sorted(changed),
                          detail={k: v for k, v in list(changed.items())[:8]})
        except Exception:  # noqa: BLE001 — observability must not break boot
            pass
    return {"changed": changed, "first_boot": first_boot}


def hlo_fingerprint(text: str) -> str:
    """Stable short fingerprint of a lowered program's HLO text."""
    return hashlib.sha256(text.encode("utf-8", "replace")).hexdigest()[:16]

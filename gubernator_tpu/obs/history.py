"""On-node metrics history: a bounded, fixed-interval time-series ring.

Every `GUBER_HISTORY_TICK_S` the ring snapshots a curated set of the
node's counters and gauges — decision/shed/eviction totals, key-table
occupancy, admission pending, lease budgets, GLOBAL queue depths, and
per-peer circuit state — into one flat sample dict. ~2 h of samples
(`GUBER_HISTORY_RETENTION`) answer "what led up to this" where /metrics
and /v1/debug/vars only answer "what is true right now":

- /v1/debug/history serves the ring to operators and tooling,
- diagnostic bundles append a history tail so a bundle carries the
  run-up to an incident, not just the instant,
- the anomaly engine's burn/rate windows read from this ring instead of
  private bookkeeping (one snapshot store per node, not two), and
- the headroom forecaster (obs/keyspace.py) fits key-table growth over
  it to project time-to-full.

Samples are cumulative counters plus instantaneous gauges; consumers
diff counters between samples, never read them as rates. Collection is
one pass of attribute reads and dict sums — no device work, no locks
held across subsystems — so a tick costs microseconds and is safe from
any thread. `GUBER_HISTORY=0` keeps the ring alive for the anomaly
engine (clamped to its slow-window needs) but stops the background
ticker, the endpoint tail, and the bundle tail.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from gubernator_tpu.obs import witness
from gubernator_tpu.obs.profile import background_of

log = logging.getLogger("gubernator_tpu.history")

# v2: samples carry the profiling plane's cumulative columns
# (profile_<phase>_s per serving-cycle phase, profile_lock_wait_s,
# profile_cycles) — consumers diff them between samples like every
# other counter column.
# v3: samples carry the decision-ledger columns (ledger_violations,
# ledger_overshoot_hits, ledger_minted_budget — cumulative) so bundles
# and the anomaly windows show the over-admission run-up, not just the
# audited instant.
HISTORY_SCHEMA_VERSION = 3

# retention floor when the ring is disabled: the anomaly engine still
# serves its burn windows (default slow window 600 s) from here
_MIN_RETENTION_S = 900.0


class MetricsHistory:
    """Fixed-interval ring of signal snapshots for one Instance."""

    def __init__(self, instance, tick_s: float = 5.0,
                 retention_s: float = 7200.0, enabled: bool = True,
                 anomaly=None):
        self.instance = instance
        self.tick_s = max(float(tick_s), 0.05)
        self.enabled = bool(enabled)
        retention_s = float(retention_s)
        if not self.enabled:
            retention_s = min(retention_s, _MIN_RETENTION_S)
        self.retention_s = max(retention_s, self.tick_s)
        # the anomaly engine owning the SLO counters; backfilled by
        # AnomalyEngine.__init__ when the Instance wires a shared ring
        self.anomaly = anomaly
        self._lock = witness.make_lock("history.ring")
        maxlen = int(self.retention_s / self.tick_s) + 8
        self._samples: "deque[Dict[str, float]]" = deque(maxlen=maxlen)
        self.ticks = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -------------------------------------------------------- collection

    def collect(self, now: Optional[float] = None) -> Dict[str, float]:
        """One snapshot of the curated signal set. Pure attribute reads;
        every subsystem is optional so stub instances collect zeros."""
        now = time.monotonic() if now is None else now
        inst = self.instance
        sig: Dict[str, float] = {"t": float(now), "wall": time.time()}

        stats = getattr(getattr(inst, "backend", None), "stats", None)
        if stats is not None:
            d = stats.as_dict() if hasattr(stats, "as_dict") else dict(stats)
            sig["decisions"] = float(d.get("requests", 0))
            sig["over_limit"] = float(d.get("over_limit", 0))
        else:
            sig["decisions"] = 0.0
            sig["over_limit"] = 0.0

        sig["deadline_expired"] = float(
            sum(getattr(inst, "deadline_expired_stats", {}).values()))
        adm = getattr(inst, "admission", None)
        sig["sheds"] = float(sum(adm.stats.values())) if adm is not None \
            else 0.0
        sig["admission_pending"] = float(adm.pending()) \
            if adm is not None else 0.0
        pls = getattr(inst, "peerlink_service", None)
        sig["pull_boundary_stalls"] = float(
            pls.stats.get("pull_boundary_stalls", 0)) if pls is not None \
            else 0.0

        lm = getattr(inst, "leases", None)
        if lm is not None:
            sig["lease_fail_close"] = float(lm.stats.get("expired_held", 0))
            if getattr(lm, "enabled", False):
                sig["lease_outstanding"] = float(lm.outstanding())
                sig["lease_held_keys"] = float(lm.held_count())
            else:
                sig["lease_outstanding"] = 0.0
                sig["lease_held_keys"] = 0.0
        else:
            sig["lease_fail_close"] = 0.0
            sig["lease_outstanding"] = 0.0
            sig["lease_held_keys"] = 0.0

        led = getattr(inst, "ledger", None)
        if led is not None and getattr(led, "enabled", False):
            lt = led.totals()
            sig["ledger_violations"] = float(lt.get("violations", 0))
            sig["ledger_overshoot_hits"] = float(
                lt.get("overshoot_hits", 0))
            sig["ledger_minted_budget"] = float(lt.get("minted_budget", 0))
        else:
            sig["ledger_violations"] = 0.0
            sig["ledger_overshoot_hits"] = 0.0
            sig["ledger_minted_budget"] = 0.0

        from gubernator_tpu.obs.introspect import (
            eviction_count,
            key_table_size,
        )

        backend = getattr(inst, "backend", None)
        occ = key_table_size(backend) if backend is not None else None
        sig["key_count"] = float(occ) if occ is not None else 0.0
        ev = eviction_count(backend) if backend is not None else None
        sig["evictions"] = float(ev) if ev is not None else 0.0

        gm = getattr(inst, "global_manager", None)
        if gm is not None:
            hits_depth, bcast_depth = gm.depths()
            sig["global_hits_depth"] = float(hits_depth)
            sig["global_broadcast_depth"] = float(bcast_depth)
        else:
            sig["global_hits_depth"] = 0.0
            sig["global_broadcast_depth"] = 0.0

        open_peers: List[str] = []
        all_peers = getattr(inst, "all_peer_clients", None)
        if callable(all_peers):
            for p in all_peers():
                c = getattr(p, "circuit", None)
                if c is not None and getattr(c, "state_name", "") != "closed":
                    open_peers.append(
                        f"{p.info.address}:{c.state_name}")
        sig["circuits_open"] = float(len(open_peers))
        if open_peers:  # per-peer state, only when non-trivial
            sig["circuit_peers"] = sorted(open_peers)  # type: ignore[assignment]

        prof = getattr(inst, "profiler", None) \
            or getattr(backend, "profiler", None)
        if prof is not None:
            totals = prof.totals()
            for phase, t in totals.items():
                sig[f"profile_{phase}_s"] = t["total_ns"] / 1e9
            # cycle count proxy: every serving cycle feeds "prep" once
            sig["profile_cycles"] = float(totals.get(
                "prep", {"n": 0})["n"])
        else:
            from gubernator_tpu.obs.profile import PHASES
            for phase in PHASES:
                sig[f"profile_{phase}_s"] = 0.0
            sig["profile_cycles"] = 0.0

        an = self.anomaly or getattr(inst, "anomaly", None)
        if an is not None and hasattr(an, "slo_snapshot"):
            total, good, errors = an.slo_snapshot()
            sig["slo_total"] = float(total)
            sig["slo_good"] = float(good)
            sig["slo_errors"] = float(errors)
        else:
            sig["slo_total"] = 0.0
            sig["slo_good"] = 0.0
            sig["slo_errors"] = 0.0
        return sig

    # --------------------------------------------------------- the ring

    def record(self, now: float, sample: Dict[str, float]) -> bool:
        """Append a collected sample when one tick has elapsed since the
        newest (fixed-interval semantics: callers at any cadence — the
        anomaly sweep, the scrape piggyback, the ticker — share one ring
        without densifying it). Returns whether the sample was kept."""
        with self._lock:
            if self._samples and now - self._samples[-1]["t"] \
                    < self.tick_s * 0.9:
                return False
            self._samples.append(sample)
            self.ticks += 1
            horizon = now - self.retention_s
            while len(self._samples) > 2 and self._samples[0]["t"] < horizon:
                self._samples.popleft()
        return True

    def tick(self, now: Optional[float] = None) -> bool:
        """Collect + record one sample when due."""
        now = time.monotonic() if now is None else now
        with self._lock:
            if self._samples and now - self._samples[-1]["t"] \
                    < self.tick_s * 0.9:
                return False
        with background_of(self.instance, "history.sample"):
            return self.record(now, self.collect(now))

    def window_snap(self, t_floor: float) -> Optional[Dict[str, float]]:
        """Newest sample at/older than t_floor, else the oldest held —
        a young ring serves the history it has. None when empty."""
        with self._lock:
            if not self._samples:
                return None
            chosen = self._samples[0]
            for s in self._samples:
                if s["t"] <= t_floor:
                    chosen = s
                else:
                    break
            return chosen

    def latest(self) -> Optional[Dict[str, float]]:
        with self._lock:
            return self._samples[-1] if self._samples else None

    def tail(self, n: int = 0) -> List[Dict[str, float]]:
        """Newest-last copy of the ring (the whole ring when n<=0)."""
        with self._lock:
            samples = list(self._samples)
        return samples[-n:] if n > 0 else samples

    def series(self, field: str) -> List[tuple]:
        """(t, value) pairs for one signal — forecaster fodder."""
        with self._lock:
            return [(s["t"], s.get(field, 0.0)) for s in self._samples]

    def sample_count(self) -> int:
        with self._lock:
            return len(self._samples)

    # --------------------------------------------------------- lifecycle

    def start(self) -> None:
        """Daemon mode: a background ticker keeps the ring dense even
        with no scrapes or health probes arriving. No-op when disabled."""
        if not self.enabled or self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, name="history",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=2.0)

    def _run(self) -> None:
        while not self._stop.wait(self.tick_s):
            try:
                self.tick()
            except Exception:  # noqa: BLE001 — the ring must survive
                log.exception("history tick failed")

    # ------------------------------------------------------- inspection

    def debug(self) -> dict:
        """The /v1/debug/vars "history" section: shape, not samples
        (the full ring lives at /v1/debug/history)."""
        with self._lock:
            n = len(self._samples)
            span = (self._samples[-1]["t"] - self._samples[0]["t"]) \
                if n > 1 else 0.0
            newest = dict(self._samples[-1]) if n else None
        return {
            "enabled": self.enabled,
            "tick_s": self.tick_s,
            "retention_s": self.retention_s,
            "samples": n,
            "span_s": round(span, 3),
            "ticks": self.ticks,
            "newest": newest,
        }

    def endpoint_body(self, n: int = 0) -> dict:
        """The /v1/debug/history response."""
        samples = self.tail(n) if self.enabled else []
        return {
            "schema_version": HISTORY_SCHEMA_VERSION,
            "enabled": self.enabled,
            "tick_s": self.tick_s,
            "retention_s": self.retention_s,
            "sample_count": self.sample_count(),
            "samples": samples,
        }

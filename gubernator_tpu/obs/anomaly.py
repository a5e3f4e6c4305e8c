"""Anomaly watchers: detectors over the live subsystem counters plus a
multi-window SLO burn-rate engine (Google SRE workbook ch. 5).

Each detector reads signals the node already maintains — nothing here
adds work to the serving path beyond one `observe()` call per client
batch. Detections are edge-triggered: a rising edge emits a
flight-recorder event, flips the `anomaly_active{detector}` gauge,
annotates health_check, and (when a BundleWriter is wired) captures a
diagnostic bundle so the incident state survives the incident.

Detectors:

- ``deadline_burst``     deadline-expired drops per second over threshold
- ``shed_spike``         admission sheds per second over threshold
- ``circuit_open``       any peer circuit currently open
- ``stall_regression``   peerlink pull-boundary stalls per second over
                         threshold while wire v2 is negotiated (v2's whole
                         win is making these ~0; a regression means the
                         cross-pull pipeline stopped overlapping)
- ``lease_fail_close``   lease fail-close (expired_held) per second over
                         threshold — owner unreachable AND leases dying
- ``slo_burn``           decision-latency/error budget burning faster than
                         `burn_fast_threshold` over the fast window AND
                         `burn_slow_threshold` over the slow window (the
                         two-window AND suppresses blips and stale pages)
- ``capacity``           the headroom forecaster (obs/keyspace.py)
                         projects the key table full within
                         `capacity_horizon_s`, with the table already past
                         its occupancy floor — eviction amnesty is coming
                         and the operator should reshard or tier first
- ``profile_shift``      the serving-cycle decomposition (obs/profile.py)
                         moved: some phase's share of serial cycle time
                         over the fast window differs from its slow-window
                         baseline by more than `profile_shift_threshold`
                         absolute, with enough cycles in both windows to
                         trust the shares — a recompile, lock convoy, or
                         host-side regression changed WHERE time goes
                         even if total latency still looks fine
- ``over_admission``     the decision ledger's conservation audit
                         (obs/ledger.py) found a key-window whose summed
                         admits exceeded limit + installed lease budget +
                         declared authority slack — budget was minted,
                         the one thing every delegation tier promises
                         never happens. The sweep drives the audit
                         itself (maybe_audit, off the serving path), so
                         detection needs no extra ticker

Burn/rate windows are served from the node's metrics history ring
(obs/history.py): the engine holds only the previous sweep's snapshot
for rate deltas, everything older is read back from the shared ring —
one snapshot store per node, and a bundle's history tail shows exactly
what the detectors saw.

The engine runs without a thread: ``maybe_check()`` piggybacks on
health_check and metric scrapes, so in-process harness clusters get live
detection; daemons also run ``start()``'s background ticker.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, List, Optional

from gubernator_tpu.obs import witness
from gubernator_tpu.obs.history import MetricsHistory
from gubernator_tpu.obs.profile import background_of

log = logging.getLogger("gubernator_tpu.anomaly")

DETECTORS = ("deadline_burst", "shed_spike", "circuit_open",
             "stall_regression", "lease_fail_close", "slo_burn",
             "capacity", "profile_shift", "over_admission")


class AnomalyEngine:
    """Periodic detector sweep + SLO burn-rate accounting for one
    Instance. Thresholds are rates (events/second) unless noted."""

    def __init__(self, instance, metrics=None, recorder=None,
                 interval_s: float = 5.0,
                 slo_target_ms: float = 250.0,
                 slo_objective: float = 0.999,
                 burn_fast_window_s: float = 60.0,
                 burn_slow_window_s: float = 600.0,
                 burn_fast_threshold: float = 10.0,
                 burn_slow_threshold: float = 2.0,
                 deadline_rate: float = 5.0,
                 shed_rate: float = 10.0,
                 stall_rate: float = 50.0,
                 fail_close_rate: float = 5.0,
                 history: Optional[MetricsHistory] = None,
                 capacity_horizon_s: float = 1800.0,
                 profile_shift_threshold: float = 0.15,
                 profile_min_cycles: float = 50.0):
        self.instance = instance
        self.metrics = metrics
        self.recorder = recorder
        self.capacity_horizon_s = float(capacity_horizon_s)
        self.profile_shift_threshold = float(profile_shift_threshold)
        self.profile_min_cycles = float(profile_min_cycles)
        self.interval_s = max(float(interval_s), 0.05)
        self.slo_target_ms = float(slo_target_ms)
        self.slo_objective = float(slo_objective)
        self.burn_fast_window_s = float(burn_fast_window_s)
        self.burn_slow_window_s = float(burn_slow_window_s)
        self.burn_fast_threshold = float(burn_fast_threshold)
        self.burn_slow_threshold = float(burn_slow_threshold)
        self.rates = {"deadline_burst": float(deadline_rate),
                      "shed_spike": float(shed_rate),
                      "stall_regression": float(stall_rate),
                      "lease_fail_close": float(fail_close_rate)}

        self._lock = witness.make_lock("anomaly.engine")
        # SLO accounting fed by the serving path (Instance.get_rate_limits)
        self._slo_total = 0
        self._slo_good = 0
        self._slo_errors = 0
        # the burn/rate windows read from the node's history ring; a
        # standalone engine (unit tests, stub instances) grows a private
        # ring at its own check cadence
        self.history = history if history is not None else MetricsHistory(
            instance, tick_s=max(float(interval_s), 0.05),
            anomaly=self)
        if self.history.anomaly is None:
            self.history.anomaly = self
        # previous sweep's snapshot: event rates are the delta since the
        # LAST check regardless of the ring's (coarser) tick cadence
        self._prev: Optional[tuple] = None
        self.active: Dict[str, bool] = {d: False for d in DETECTORS}
        self.detail: Dict[str, str] = {}
        self.trips: Dict[str, int] = {d: 0 for d in DETECTORS}
        self.burn_fast = 0.0
        self.burn_slow = 0.0
        self._last_check = 0.0
        self.checks = 0
        # conservation-audit edge state: violations counted at the last
        # sweep, so a sweep flags only NEW over-admission findings
        self._prev_violations = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------ serving feed

    def observe(self, latency_ms: float, error: bool = False) -> None:
        """One client batch decided: feed the SLO counters. Called on the
        serving path — two int adds under a lock held for nanoseconds."""
        with self._lock:
            self._slo_total += 1
            if error:
                self._slo_errors += 1
            elif latency_ms <= self.slo_target_ms:
                self._slo_good += 1

    # ---------------------------------------------------------- signals

    def slo_snapshot(self) -> tuple:
        """(total, good, errors) under the lock — the history ring folds
        these into every sample so burn windows read back from it."""
        with self._lock:
            return self._slo_total, self._slo_good, self._slo_errors

    def _open_circuits(self) -> List[str]:
        all_peers = getattr(self.instance, "all_peer_clients", None)
        if not callable(all_peers):
            return []
        out = []
        for p in all_peers():
            c = getattr(p, "circuit", None)
            if c is not None and getattr(c, "state_name", "") == "open":
                out.append(p.info.address)
        return out

    @staticmethod
    def _burn(cur: Dict[str, float], old: Dict[str, float],
              budget: float) -> float:
        """Error-budget burn multiplier over the snapshot span: observed
        bad fraction / allowed bad fraction. 1.0 = burning exactly at
        budget; 0 when no traffic."""
        total = cur["slo_total"] - old["slo_total"]
        if total <= 0:
            return 0.0
        good = cur["slo_good"] - old["slo_good"]
        bad_frac = max(total - good, 0.0) / total
        return bad_frac / max(budget, 1e-9)

    # ------------------------------------------------------------ checks

    def maybe_check(self) -> None:
        """Piggyback hook (health_check, metric scrape): run a sweep when
        one interval elapsed since the last, whoever the caller was."""
        if time.monotonic() - self._last_check >= self.interval_s:
            self.check()

    def check(self, now: Optional[float] = None) -> Dict[str, bool]:
        """One detector sweep; returns the active map. Thread-safe but
        sweeps are serialized — concurrent callers coalesce."""
        with background_of(self.instance, "anomaly.check"):
            return self._check(now)

    def _check(self, now: Optional[float]) -> Dict[str, bool]:
        now = time.monotonic() if now is None else now
        cur = self.history.collect(now)
        with self._lock:
            if self._last_check and now - self._last_check < 0.01:
                return dict(self.active)  # coalesced concurrent sweep
            prev = self._prev
            self._prev = (now, cur)
            self._last_check = now
            self.checks += 1
        # the sweep doubles as a ring tick (fixed-interval: the ring
        # keeps its own cadence when checks run faster than its tick)
        self.history.record(now, cur)
        fast_old = self.history.window_snap(
            now - self.burn_fast_window_s) or cur
        slow_old = self.history.window_snap(
            now - self.burn_slow_window_s) or cur

        budget = 1.0 - self.slo_objective
        self.burn_fast = self._burn(cur, fast_old, budget)
        self.burn_slow = self._burn(cur, slow_old, budget)

        found: Dict[str, bool] = {d: False for d in DETECTORS}
        detail: Dict[str, str] = {}
        if prev is not None:
            dt = max(now - prev[0], 1e-6)
            old = prev[1]
            for name, key in (("deadline_burst", "deadline_expired"),
                              ("shed_spike", "sheds"),
                              ("stall_regression", "pull_boundary_stalls"),
                              ("lease_fail_close", "lease_fail_close")):
                rate = (cur[key] - old[key]) / dt
                if rate > self.rates[name]:
                    found[name] = True
                    detail[name] = f"{rate:.1f}/s over {self.rates[name]:g}/s"
        open_peers = self._open_circuits()
        if open_peers:
            found["circuit_open"] = True
            detail["circuit_open"] = ",".join(sorted(open_peers)[:4])
        if (self.burn_fast >= self.burn_fast_threshold
                and self.burn_slow >= self.burn_slow_threshold):
            found["slo_burn"] = True
            detail["slo_burn"] = (f"burn {self.burn_fast:.1f}x fast / "
                                  f"{self.burn_slow:.1f}x slow")
        cap_detail = self._capacity_signal()
        if cap_detail:
            found["capacity"] = True
            detail["capacity"] = cap_detail
        shift_detail = self._profile_shift_signal(cur, fast_old, slow_old)
        if shift_detail:
            found["profile_shift"] = True
            detail["profile_shift"] = shift_detail
        over_detail = self._over_admission_signal()
        if over_detail:
            found["over_admission"] = True
            detail["over_admission"] = over_detail

        self._apply(found, detail)
        return found

    def _capacity_signal(self) -> str:
        """Headroom check: "" when quiet, else the firing detail. Reads
        the cartographer's forecast over the history ring — no device
        work — and stays quiet below the occupancy floor (a young
        table's first fill slope projects meaningless exhaustion)."""
        carto = getattr(self.instance, "keyspace", None)
        if carto is None:
            return ""
        try:
            from gubernator_tpu.obs.keyspace import CAPACITY_OCCUPANCY_FLOOR

            fc = carto.forecast()
        except Exception:  # noqa: BLE001 — forecasting must not break
            return ""      # detection
        if not fc.get("projectable"):
            return ""
        ttf = fc.get("time_to_full_s")
        fill = fc.get("fill_fraction") or 0.0
        if ttf is None or ttf > self.capacity_horizon_s \
                or fill < CAPACITY_OCCUPANCY_FLOOR:
            return ""
        ttp = fc.get("time_to_pressure_s")
        return (f"table full in ~{ttf:.0f}s at "
                f"{fc.get('growth_keys_per_s') or 0.0:.2f} keys/s "
                f"({fill:.0%} full"
                + (f", eviction pressure in ~{ttp:.0f}s"
                   if ttp is not None else "") + ")")

    def _profile_shift_signal(self, cur: Dict[str, float],
                              fast_old: Dict[str, float],
                              slow_old: Dict[str, float]) -> str:
        """Decomposition drift: "" when quiet, else the firing detail.
        Compares each serial phase's share of serial cycle time over the
        fast window against the slow-window baseline — both derived by
        diffing the ring's cumulative profile_* columns, so the signal
        costs attribute reads and never touches the profiler itself."""
        try:
            from gubernator_tpu.obs.profile import SERIAL_PHASES
        except Exception:  # noqa: BLE001 — detection must not break
            return ""
        if "profile_cycles" not in cur:
            return ""
        recent_cycles = cur.get("profile_cycles", 0.0) \
            - fast_old.get("profile_cycles", 0.0)
        base_cycles = fast_old.get("profile_cycles", 0.0) \
            - slow_old.get("profile_cycles", 0.0)
        # traffic guard: shares over a handful of cycles are noise
        if recent_cycles < self.profile_min_cycles \
                or base_cycles < self.profile_min_cycles:
            return ""

        def shares(new, old):
            deltas = {p: max(new.get(f"profile_{p}_s", 0.0)
                             - old.get(f"profile_{p}_s", 0.0), 0.0)
                      for p in SERIAL_PHASES}
            total = sum(deltas.values())
            if total <= 0:
                return None
            return {p: d / total for p, d in deltas.items()}

        recent = shares(cur, fast_old)
        base = shares(fast_old, slow_old)
        if recent is None or base is None:
            return ""
        worst, worst_p = 0.0, ""
        for p in SERIAL_PHASES:
            d = recent[p] - base[p]
            if abs(d) > abs(worst):
                worst, worst_p = d, p
        if abs(worst) < self.profile_shift_threshold:
            return ""
        return (f"{worst_p} share {base[worst_p]:.0%} -> "
                f"{recent[worst_p]:.0%} over fast window")

    def _over_admission_signal(self) -> str:
        """Conservation-audit check: "" when quiet, else the firing
        detail. The sweep itself drives the ledger's off-path audit
        (rate-limited inside maybe_audit), then flags NEW violations
        since the previous sweep — edge semantics, so the rising edge
        emits one event and captures one bundle per finding burst."""
        led = getattr(self.instance, "ledger", None)
        if led is None or not getattr(led, "enabled", False):
            return ""
        try:
            led.maybe_audit(getattr(self.instance, "backend", None))
            totals = led.totals()
        except Exception:  # noqa: BLE001 — auditing must not break detection
            log.exception("ledger audit failed")
            return ""
        v = int(totals.get("violations", 0))
        prev, self._prev_violations = self._prev_violations, v
        if v <= prev:
            return ""
        return (f"{v - prev} conservation violation(s), max overshoot "
                f"{int(totals.get('max_overshoot', 0))} hits")

    def _apply(self, found: Dict[str, bool], detail: Dict[str, str]) -> None:
        for name in DETECTORS:
            was, now_on = self.active[name], found[name]
            self.active[name] = now_on
            if now_on:
                self.detail[name] = detail.get(name, "")
            else:
                self.detail.pop(name, None)
            if now_on and not was:
                self.trips[name] += 1
                log.warning("anomaly %s: %s", name, detail.get(name, ""))
                if self.recorder is not None:
                    self.recorder.emit(f"anomaly.{name}",
                                       detail=detail.get(name, ""))
                self._trigger_bundle(name)
            elif was and not now_on:
                log.info("anomaly %s cleared", name)
                if self.recorder is not None:
                    self.recorder.emit("anomaly.clear", detector=name)
        self._export_gauges()

    def _trigger_bundle(self, name: str) -> None:
        writer = getattr(self.instance, "bundle_writer", None)
        if writer is None:
            return
        try:
            writer.write_for(self.instance, reason=f"anomaly:{name}",
                             metrics=self.metrics)
        except Exception:  # noqa: BLE001 — capture must not break detection
            log.exception("anomaly bundle capture failed")

    def _export_gauges(self) -> None:
        m = self.metrics
        if m is None:
            return
        try:
            for name in DETECTORS:
                m.anomaly_active.labels(detector=name).set(
                    1 if self.active[name] else 0)
            m.slo_burn_rate.labels(window="fast").set(self.burn_fast)
            m.slo_burn_rate.labels(window="slow").set(self.burn_slow)
        except Exception:  # noqa: BLE001 — metrics must not break detection
            pass

    # --------------------------------------------------------- lifecycle

    def start(self) -> None:
        """Daemon mode: a background ticker sweeps every interval even
        with no scrapes or health probes arriving."""
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, name="anomaly",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=2.0)

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.check()
            except Exception:  # noqa: BLE001 — the watcher must survive
                log.exception("anomaly sweep failed")

    # ------------------------------------------------------- inspection

    def health_note(self) -> str:
        """Health annotation, "" when quiet — annotation only: anomalies
        flag investigation-worthy state, they never flip a node unhealthy
        by themselves (the conditions that should do that already do)."""
        on = [d for d in DETECTORS if self.active[d]]
        if not on:
            return ""
        parts = [f"{d}({self.detail[d]})" if self.detail.get(d) else d
                 for d in on]
        return "anomaly: " + ", ".join(parts)

    def debug(self) -> dict:
        """The /v1/debug/vars "anomaly" section."""
        with self._lock:
            slo = {"target_ms": self.slo_target_ms,
                   "objective": self.slo_objective,
                   "total": self._slo_total, "good": self._slo_good,
                   "errors": self._slo_errors}
        return {
            "interval_s": self.interval_s,
            "capacity_horizon_s": self.capacity_horizon_s,
            "checks": self.checks,
            "active": [d for d in DETECTORS if self.active[d]],
            "detail": dict(self.detail),
            "trips": dict(self.trips),
            "slo": slo,
            "burn_fast": round(self.burn_fast, 3),
            "burn_slow": round(self.burn_slow, 3),
        }

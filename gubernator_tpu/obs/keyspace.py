"""Keyspace cartographer + headroom forecaster.

Answers the capacity questions the ROADMAP's scale-out items (live
resharding, tiered capacity, mesh placement) depend on, from data the
device table already maintains: column 7 of every row is the key's
lifetime attempted-hit counter (ops/decide.py accumulates every round's
requested hits there), and the host key directory's reverse walk
(`Engine.resolve_slots`, built for the hot-key lease tier) maps the top
slots back to key strings.

A harvest runs OFF the serving path — one device column fetch plus host
numpy — every `GUBER_KEYSPACE_INTERVAL`, and yields:

- top-K heavy hitters (key, hits, share of tracked hit mass),
- hit-mass concentration: top-1/10/100 share + a Zipf exponent estimate
  fitted over the head of the rank/count curve,
- occupancy vs capacity and cumulative eviction pressure,
- per-engine/per-device HBM bytes (`state.nbytes`, plus fps/touch for
  the devdir engine and per-shard bytes on the mesh).

Counts are lifetime attempts, so a slot recycled by LRU eviction briefly
carries its previous key's total until the new key's first round
overwrites the row — harvest-to-harvest deltas, not absolutes, are the
skew signal under churn.

The headroom forecaster fits key-table net growth over the metrics
history ring (obs/history.py) into projected time-to-full and
time-to-eviction-pressure; the anomaly engine's `capacity` detector
fires when the projection crosses `GUBER_CAPACITY_HORIZON` with the
table already past its occupancy floor.

`GUBER_KEYSPACE_SCAN=0` disables harvesting entirely (the endpoint then
reports `enabled: false`); the forecaster keeps working — it reads the
history ring, not the table.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from gubernator_tpu.models.keyspace import resolve_slots
from gubernator_tpu.obs import witness
from gubernator_tpu.obs.profile import background_of
from gubernator_tpu.obs.introspect import (
    eviction_count,
    key_table_size,
    table_capacity,
)
from gubernator_tpu.ops.decide import ROW_HITS, fetch_column

log = logging.getLogger("gubernator_tpu.keyspace")

KEYSPACE_SCHEMA_VERSION = 1

# occupancy floor below which the capacity detector stays quiet: a young
# table's first fill slope projects "exhaustion" long before the
# projection means anything
CAPACITY_OCCUPANCY_FLOOR = 0.5


# --------------------------------------------------------------- analysis


# rows of the hit column analysed at a time: beside the column itself a
# harvest holds a chunk's temporaries, not the table's (its whole-column
# copy, compaction, index and partition arrays were +335 to +390 MB of
# resident set for a tenth of a second at 10M rows; PERF.md section 6)
_CHUNK = 1 << 20


def _heaviest(counts: np.ndarray, m: int):
    """(slots, total, nonzero): the `m` slots with the most hits among
    those that have any, heaviest first; the hits of all that have any;
    how many those are. One pass over `counts`, `_CHUNK` rows at a time."""
    best = np.empty(0, np.int64)
    total, nonzero = 0.0, 0
    for lo in range(0, counts.size, _CHUNK):
        part = counts[lo:lo + _CHUNK]
        live = np.flatnonzero(part > 0)
        if not live.size:
            continue
        hits = part[live]
        total += float(hits.sum())
        nonzero += int(live.size)
        if live.size > m:
            live = live[np.argpartition(hits, -m)[-m:]]
        best = np.concatenate([best, live + lo])
        if best.size > m:
            best = best[np.argpartition(counts[best], -m)[-m:]]
    best = best[np.argsort(counts[best], kind="stable")[::-1]]
    return best, total, nonzero


def concentration(counts: np.ndarray, fit_ranks: int = 100) -> dict:
    """Hit-mass concentration of one harvest's per-slot attempt counts:
    top-1/10/100 share of the tracked mass plus a Zipf exponent estimate
    (slope of log count vs log rank over the head of the curve)."""
    flat = np.asarray(counts).reshape(-1)
    slots, total, n = _heaviest(flat, max(fit_ranks, 100))
    counts = flat[slots].astype(np.float64)  # the head, heaviest first
    out = {
        "tracked_hits": int(total),
        "nonzero_slots": int(n),
        "top1_share": 0.0,
        "top10_share": 0.0,
        "top100_share": 0.0,
        "zipf_exponent": None,
    }
    if total <= 0:
        return out
    out["top1_share"] = float(counts[:1].sum() / total)
    out["top10_share"] = float(counts[:10].sum() / total)
    out["top100_share"] = float(counts[:100].sum() / total)
    head = counts[:min(fit_ranks, n)]
    if head.size >= 3:
        ranks = np.log(np.arange(1, head.size + 1, dtype=np.float64))
        vals = np.log(head)
        var = float(((ranks - ranks.mean()) ** 2).sum())
        if var > 0:
            slope = float(
                ((ranks - ranks.mean()) * (vals - vals.mean())).sum() / var)
            out["zipf_exponent"] = round(-slope, 4)
    return out


def hbm_bytes(backend) -> dict:
    """Device-memory accounting for the backend's table arrays: state
    (every engine), fps/touch (devdir), with a per-device breakdown of
    the state array's addressable shards (one entry on a single device,
    one per mesh shard on the sharded backend)."""
    arrays: Dict[str, int] = {}
    for name in ("state", "fps", "touch"):
        a = getattr(backend, name, None)
        nb = getattr(a, "nbytes", None)
        if nb is not None:
            arrays[name] = int(nb)
    per_device: List[dict] = []
    # shard walk re-reads backend.state under the engine lock: the
    # serving path donates the state buffer each dispatch, and
    # addressable_shards on a stale reference raises deleted-array
    lock = getattr(backend, "_lock", None)
    try:
        if getattr(backend, "state", None) is not None:
            if lock is not None:
                with lock:
                    for sh in backend.state.addressable_shards:
                        per_device.append(
                            {"device": str(sh.device),
                             "state_bytes": int(sh.data.nbytes)})
            else:
                # guberlint: disable=lock-discipline -- backend exposes no _lock (test stub / host table): nothing donates, nothing to hold
                for sh in backend.state.addressable_shards:
                    per_device.append({"device": str(sh.device),
                                       "state_bytes": int(sh.data.nbytes)})
    except Exception:  # noqa: BLE001 — accounting must not raise
        per_device = []
    return {"total_bytes": sum(arrays.values()), "arrays": arrays,
            "per_device": per_device}


def headroom_forecast(history, backend, pressure_fraction: float = 0.9,
                      min_samples: int = 3) -> dict:
    """Linear net-growth fit of key-table occupancy over the history
    ring -> projected time-to-full and time-to-eviction-pressure.

    time_to_full_s / time_to_pressure_s are None while the table is not
    growing (nothing to project); time_to_pressure_s is 0.0 once the
    table is already past the pressure watermark or actively evicting —
    the pressure isn't projected any more, it's here."""
    cap = table_capacity(backend) if backend is not None else None
    out: dict = {
        "projectable": False,
        "capacity": cap,
        "pressure_fraction": float(pressure_fraction),
        "samples": 0,
        "span_s": 0.0,
        "key_count": None,
        "fill_fraction": None,
        "growth_keys_per_s": None,
        "eviction_rate_per_s": None,
        "time_to_full_s": None,
        "time_to_pressure_s": None,
    }
    if history is None or cap is None or cap <= 0:
        return out
    series = history.series("key_count")
    out["samples"] = len(series)
    if len(series) < min_samples:
        return out
    ts = np.asarray([t for t, _ in series], np.float64)
    ys = np.asarray([y for _, y in series], np.float64)
    span = float(ts[-1] - ts[0])
    out["span_s"] = round(span, 3)
    if span <= 0:
        return out
    current = float(ys[-1])
    out["key_count"] = int(current)
    out["fill_fraction"] = round(current / cap, 6)
    t0 = ts - ts.mean()
    var = float((t0 ** 2).sum())
    slope = float((t0 * (ys - ys.mean())).sum() / var) if var > 0 else 0.0
    out["growth_keys_per_s"] = round(slope, 6)
    ev = history.series("evictions")
    if len(ev) >= 2:
        ev_rate = (ev[-1][1] - ev[0][1]) / span
        out["eviction_rate_per_s"] = round(float(ev_rate), 6)
    out["projectable"] = True
    pressure_at = pressure_fraction * cap
    if current >= pressure_at or (out["eviction_rate_per_s"] or 0.0) > 0:
        out["time_to_pressure_s"] = 0.0
    elif slope > 1e-9:
        out["time_to_pressure_s"] = round((pressure_at - current) / slope, 3)
    if current >= cap:
        out["time_to_full_s"] = 0.0
    elif slope > 1e-9:
        out["time_to_full_s"] = round((cap - current) / slope, 3)
    return out


# ------------------------------------------------------------ cartographer


class KeyspaceCartographer:
    """Periodic off-path harvest of the device table's keyspace shape
    for one Instance, served at /v1/debug/keyspace."""

    def __init__(self, instance, interval_s: float = 60.0,
                 top_k: int = 20, enabled: bool = True,
                 pressure_fraction: float = 0.9):
        self.instance = instance
        self.interval_s = max(float(interval_s), 0.05)
        self.top_k = max(int(top_k), 1)
        self.enabled = bool(enabled)
        self.pressure_fraction = float(pressure_fraction)
        self._lock = witness.make_lock("keyspace.cartographer")
        self._report: Optional[dict] = None
        self._last_harvest = 0.0
        self.harvests = 0
        self.errors = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ---------------------------------------------------------- harvest

    def _device_counts(self, backend):
        """Fetch column 7 (lifetime attempted hits) for every slot.
        Returns (counts, owner_capacity): counts is flat over the global
        slot space; owner_capacity is the per-owner slot stride on the
        sharded backend (None on single-table engines)."""
        if getattr(backend, "state", None) is None:
            return None, None
        lock = getattr(backend, "_lock", None)
        plan = getattr(backend, "plan", None)
        # `backend.state` must be re-read UNDER the engine lock: the
        # serving path donates the state buffer to each dispatch and
        # rebinds the attribute, so a reference captured outside the
        # lock can point at a deleted donated array by readback time
        if plan is not None:  # sharded mesh table u32[R, S, C, 16]
            if lock is not None:
                with lock:
                    arr = fetch_column(backend.state, ROW_HITS)
            else:
                # guberlint: disable=lock-discipline -- backend exposes no _lock (test stub): nothing donates, nothing to hold
                arr = fetch_column(backend.state, ROW_HITS)
            C = int(plan.capacity_per_shard)
            flat = np.empty(int(plan.n_owners) * C, np.int64)
            for o in range(int(plan.n_owners)):
                r_, s_ = plan.owner_coords(o)
                flat[o * C:(o + 1) * C] = arr[r_, s_]
            return flat, C
        if lock is not None:  # host/devdir engine table u32[C, 16]
            with lock:
                counts = fetch_column(backend.state, ROW_HITS)
        else:
            # guberlint: disable=lock-discipline -- backend exposes no _lock (test stub): nothing donates, nothing to hold
            counts = fetch_column(backend.state, ROW_HITS)
        return counts, None

    def _top_keys(self, backend, counts: np.ndarray,
                  owner_capacity) -> List[dict]:
        """Top-K slots by attempted hits, reverse-walked to key strings
        through the host directory (absent entries — recycled mid-walk
        or the devdir engine's on-chip directory — keep key=None)."""
        top, total, _ = _heaviest(counts, self.top_k)
        if top.size == 0:
            return []
        resolved: Dict[int, str] = {}
        if owner_capacity is not None:
            dirs = getattr(backend, "directories", None) or []
            by_owner: Dict[int, List[int]] = {}
            for slot in top:
                by_owner.setdefault(
                    int(slot) // owner_capacity, []).append(
                    int(slot) % owner_capacity)
            for o, local in by_owner.items():
                if o >= len(dirs):
                    continue
                for ls, key in resolve_slots(dirs[o], local).items():
                    resolved[o * owner_capacity + ls] = key
        elif getattr(backend, "fps", None) is None:
            resolve = getattr(backend, "resolve_slots", None)
            if callable(resolve):
                resolved = resolve([int(s) for s in top])
        out = []
        for slot in top:
            hits = int(counts[slot])
            entry = {"key": resolved.get(int(slot)), "slot": int(slot),
                     "hits": hits,
                     "share": round(hits / total, 6) if total else 0.0}
            if owner_capacity is not None:
                entry["owner"] = int(slot) // owner_capacity
            out.append(entry)
        return out

    def harvest(self, now: Optional[float] = None) -> Optional[dict]:
        """One full scan; returns the fresh report (None on failure).
        Serialized: concurrent callers coalesce onto one scan."""
        with background_of(self.instance, "keyspace.harvest"):
            return self._harvest(now)

    def _harvest(self, now: Optional[float]) -> Optional[dict]:
        now = time.monotonic() if now is None else now
        backend = getattr(self.instance, "backend", None)
        if backend is None:
            return None
        t0 = time.perf_counter()
        try:
            counts, owner_capacity = self._device_counts(backend)
            occ = key_table_size(backend)
            cap = table_capacity(backend)
            ev = eviction_count(backend)
            report: dict = {
                "schema_version": KEYSPACE_SCHEMA_VERSION,
                "captured_at": time.time(),
                "backend": type(backend).__name__,
                "keys_resolvable": getattr(backend, "fps", None) is None,
                "occupancy": {
                    "key_count": occ,
                    "capacity": cap,
                    "fill_fraction": round(occ / cap, 6)
                    if occ is not None and cap else None,
                    "free_slots": (cap - occ)
                    if occ is not None and cap is not None else None,
                },
                "evictions": {"total": ev},
                "hbm": hbm_bytes(backend),
            }
            if owner_capacity is not None:
                dirs = getattr(backend, "directories", None) or []
                total = sum(len(d) for d in dirs) or 1
                report["shards"] = [
                    {"owner": o, "key_count": len(d),
                     "capacity": owner_capacity,
                     "share": round(len(d) / total, 6)}
                    for o, d in enumerate(dirs)]
            if counts is not None:
                report["hit_mass"] = concentration(counts)
                report["top_keys"] = self._top_keys(
                    backend, counts, owner_capacity)
            else:
                report["hit_mass"] = None
                report["top_keys"] = []
            report["harvest_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 3)
        except Exception:  # noqa: BLE001 — cartography must not raise
            self.errors += 1
            log.exception("keyspace harvest failed")
            return None
        with self._lock:
            self._report = report
            self._last_harvest = now
            self.harvests += 1
        return report

    def maybe_harvest(self) -> None:
        """Piggyback hook (metric scrape): harvest when one interval has
        elapsed since the last — and only when the scan is enabled."""
        if not self.enabled:
            return
        with self._lock:
            due = time.monotonic() - self._last_harvest >= self.interval_s
        if due:
            self.harvest()

    def last_report(self) -> Optional[dict]:
        with self._lock:
            return self._report

    def report(self, refresh: bool = False) -> Optional[dict]:
        """Newest harvest; scans once when never harvested (or on
        refresh) and the scan is enabled."""
        with self._lock:
            have = self._report
        if (have is None or refresh) and self.enabled:
            return self.harvest() or have
        return have

    # --------------------------------------------------------- forecast

    def forecast(self) -> dict:
        """Headroom projection over the instance's history ring."""
        return headroom_forecast(
            getattr(self.instance, "history", None),
            getattr(self.instance, "backend", None),
            pressure_fraction=self.pressure_fraction)

    def endpoint_body(self) -> dict:
        """The /v1/debug/keyspace response."""
        return {
            "schema_version": KEYSPACE_SCHEMA_VERSION,
            "enabled": self.enabled,
            "interval_s": self.interval_s,
            "top_k": self.top_k,
            "report": self.report(),
            "forecast": self.forecast(),
        }

    # --------------------------------------------------------- lifecycle

    def start(self) -> None:
        """Daemon mode: background harvests every interval. No-op when
        the scan is disabled."""
        if not self.enabled or self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, name="keyspace",
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
                self.harvest()
            except Exception:  # noqa: BLE001 — the ticker must survive
                log.exception("keyspace harvest tick failed")

    # ------------------------------------------------------- inspection

    def debug(self) -> dict:
        """The /v1/debug/vars "keyspace" section: harvest bookkeeping
        plus the newest report's headline numbers (the full report lives
        at /v1/debug/keyspace)."""
        with self._lock:
            rep = self._report
        out = {
            "enabled": self.enabled,
            "interval_s": self.interval_s,
            "top_k": self.top_k,
            "harvests": self.harvests,
            "errors": self.errors,
        }
        if rep is not None:
            out["occupancy"] = rep.get("occupancy")
            out["hbm_total_bytes"] = (rep.get("hbm") or {}).get(
                "total_bytes")
            hm = rep.get("hit_mass") or {}
            out["top1_share"] = hm.get("top1_share")
            out["zipf_exponent"] = hm.get("zipf_exponent")
            out["harvest_ms"] = rep.get("harvest_ms")
        return out

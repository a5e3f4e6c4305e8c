"""Autopilot: bounded closed-loop controllers over the live knobs.

PRs 9–16 built a sensing plane — anomaly detectors, the capacity
forecaster, the keyspace cartographer, the continuous profiler — that
can *detect* exactly the conditions each serving knob exists for but
cannot act. This module closes the loop, carefully: every controller is
a sense→decide→actuate cycle with

- hysteresis: separate trip/clear thresholds plus a minimum dwell time
  on BOTH edges, so a signal flapping at the threshold produces at most
  one engage (and so at most one move per knob) per dwell window;
- rate-limited actuation: at most one move per knob per cooldown, each
  move a bounded step toward the target, never outside the knob's
  declared [floor, ceiling] band (multipliers of the boot-time baseline,
  further clamped by the knob's absolute validity range);
- a hard freeze while a reshard transfer or membership change is in
  flight: no knob moves between `reshard.plan` and `committed`/
  `aborted`, and intents accumulated before the freeze are DROPPED, not
  replayed stale — post-freeze moves require a fresh sense + dwell;
- a full audit trail: every move/clamp/freeze goes to the flight
  recorder (`autopilot.move` / `autopilot.clamp` / `autopilot.freeze`)
  with the triggering signal attached, so a bundle shows *why* the
  system reconfigured itself.

Actuation goes through `conf.behaviors` (and the two live subsystem
attributes, cartographer interval and pipeline depth) — all of which
the serving path already reads live per use — so engaging the autopilot
changes no serving code. GUBER_AUTOPILOT=0 (the default) keeps every
hook a single attribute test and the decision stream bit-identical to
the static-knob tree (tests/test_autopilot.py differential).

The controller/knob registries below are module-level literals on
purpose: guberlint's `controller-bounds` rule parses them from the AST
and fails the build when a controller actuates a knob with no declared
floor/ceiling/step or whose env knob is missing from the operator docs.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from typing import Callable, Dict, Optional, Tuple

from gubernator_tpu.obs import witness
from gubernator_tpu.obs.profile import background_of

log = logging.getLogger("gubernator_tpu.autopilot")

# flight-recorder kinds (docs/observability.md "Flight recorder")
EV_MOVE = "autopilot.move"
EV_CLAMP = "autopilot.clamp"
EV_FREEZE = "autopilot.freeze"


@dataclasses.dataclass(frozen=True)
class KnobSpec:
    """Declared actuation bounds for one controller-movable knob.

    `floor`/`ceiling`/`step` are multipliers of the knob's boot-time
    baseline (captured at first actuation-eligible tick), so one spec
    covers every deployment size; `abs_floor`/`abs_ceiling` additionally
    pin the knob inside its absolute validity range (e.g. a fraction can
    never exceed 1.0 no matter the baseline)."""

    name: str
    env: str
    floor: float
    ceiling: float
    step: float
    integer: bool = False
    abs_floor: Optional[float] = None
    abs_ceiling: Optional[float] = None


# The central knob registry: every knob any controller may touch MUST
# appear here with explicit bounds (guberlint `controller-bounds`).
KNOBS: Dict[str, KnobSpec] = {
    "max_pending": KnobSpec(
        name="max_pending", env="GUBER_MAX_PENDING",
        floor=1.0, ceiling=2.0, step=0.25, integer=True, abs_floor=1),
    "hot_lease_fraction": KnobSpec(
        name="hot_lease_fraction", env="GUBER_HOT_LEASE_FRACTION",
        floor=1.0, ceiling=2.5, step=0.5, abs_ceiling=1.0),
    "hot_lease_ttl_s": KnobSpec(
        name="hot_lease_ttl_s", env="GUBER_HOT_LEASE_TTL",
        floor=1.0, ceiling=3.0, step=0.5),
    "keyspace_interval_s": KnobSpec(
        name="keyspace_interval_s", env="GUBER_KEYSPACE_INTERVAL",
        floor=0.25, ceiling=1.0, step=0.25, abs_floor=0.05),
    "pipeline_depth": KnobSpec(
        name="pipeline_depth", env="GUBER_PIPELINE_DEPTH",
        floor=0.5, ceiling=2.0, step=0.4, integer=True, abs_floor=1),
}

# The controller registry: which signal moves which knobs, and toward
# which side of the band while engaged ("ceiling" = raise toward
# baseline*ceiling, "floor" = lower toward baseline*floor; disengaged
# controllers always decay back toward the baseline). Pure literal —
# guberlint cross-checks every entry against KNOBS.
CONTROLLERS = (
    {"name": "admission", "knobs": ("max_pending",), "side": "ceiling",
     "signal": "admission.pending_fraction",
     "trip": None, "clear": None},  # trip = live brownout_fraction
    {"name": "hotkey",
     "knobs": ("hot_lease_fraction", "hot_lease_ttl_s"),
     "side": "ceiling", "signal": "keyspace.top1_share",
     "trip": 0.35, "clear": 0.20},
    {"name": "capacity", "knobs": ("keyspace_interval_s",),
     "side": "floor", "signal": "capacity.horizon_ratio",
     "trip": 1.0, "clear": 0.5},
    {"name": "pipeline", "knobs": ("pipeline_depth",), "side": "ceiling",
     "signal": "pipeline.pressure",
     "trip": 1.0, "clear": 0.25},
)


class _KnobState:
    """Per-knob actuation bookkeeping (baseline, cooldown clock)."""

    __slots__ = ("spec", "baseline", "last_move", "moves", "last_event")

    def __init__(self, spec: KnobSpec):
        self.spec = spec
        self.baseline: Optional[float] = None  # captured lazily
        self.last_move: float = 0.0            # monotonic; 0 = never
        self.moves: int = 0
        self.last_event: Optional[dict] = None

    def band(self) -> Tuple[float, float]:
        """Absolute [lo, hi] the knob may occupy (baseline captured)."""
        s, b = self.spec, self.baseline
        lo, hi = b * s.floor, b * s.ceiling
        if s.abs_floor is not None:
            lo = max(lo, s.abs_floor)
        if s.abs_ceiling is not None:
            hi = min(hi, s.abs_ceiling)
        return lo, max(hi, lo)


class _Controller:
    """One sense→decide→actuate loop with two-edge hysteresis."""

    def __init__(self, reg: dict, sense: Callable[[], Optional[float]],
                 knobs: Dict[str, _KnobState]):
        self.name: str = reg["name"]
        self.signal: str = reg["signal"]
        self.side: str = reg["side"]
        self.trip: Optional[float] = reg["trip"]
        self.clear: Optional[float] = reg["clear"]
        self.sense = sense
        self.knobs = knobs
        self.engaged = False
        self.trip_since: Optional[float] = None
        self.clear_since: Optional[float] = None
        self.value: Optional[float] = None
        self.engages = 0

    def thresholds(self) -> Tuple[float, float]:
        return float(self.trip), float(self.clear)

    def decide(self, now: float, dwell_s: float) -> None:
        """Advance the hysteresis state machine one tick. `value` was
        just sensed; None (signal unavailable) reads as fully clear."""
        trip, clear = self.thresholds()
        v = self.value if self.value is not None else 0.0
        if not self.engaged:
            self.clear_since = None
            if v >= trip:
                if self.trip_since is None:
                    self.trip_since = now
                if now - self.trip_since >= dwell_s:
                    self.engaged = True
                    self.engages += 1
                    self.trip_since = None
            else:
                # anywhere below trip: the dwell clock restarts — a
                # flapping signal never accumulates dwell credit
                self.trip_since = None
        else:
            self.trip_since = None
            if v <= clear:
                if self.clear_since is None:
                    self.clear_since = now
                if now - self.clear_since >= dwell_s:
                    self.engaged = False
                    self.clear_since = None
            else:
                self.clear_since = None

    def drop_intent(self) -> bool:
        """Freeze semantics: forget any accumulated dwell credit so a
        post-freeze move needs a fresh sense + full dwell. Returns True
        when there was an in-flight intent to drop."""
        had = self.trip_since is not None or self.clear_since is not None
        self.trip_since = self.clear_since = None
        return had

    def debug(self, now: float) -> dict:
        out = {
            "engaged": self.engaged,
            "armed": self.trip_since is not None,
            "dwelling": (self.trip_since is not None
                         or self.clear_since is not None),
            "signal": self.signal,
            "value": self.value,
            "trip": self.thresholds()[0],
            "clear": self.thresholds()[1],
            "engages": self.engages,
            "knobs": {},
            "last_move": None,
        }
        for kname, ks in self.knobs.items():
            lo, hi = (None, None)
            if ks.baseline is not None:
                lo, hi = ks.band()
            out["knobs"][kname] = {
                "baseline": ks.baseline,
                "floor": lo,
                "ceiling": hi,
                "step": ks.spec.step,
                "moves": ks.moves,
                "last_move_age_s": (round(now - ks.last_move, 3)
                                    if ks.last_move else None),
            }
            if ks.last_event is not None:
                lm = out["last_move"]
                if lm is None or ks.last_event["t"] > lm["t"]:
                    out["last_move"] = ks.last_event
        return out


class Autopilot:
    """Bounded closed-loop controller sweep for one Instance.

    Mirrors the AnomalyEngine's tick contract: ``maybe_tick()``
    piggybacks on metric scrapes and the scenario runner's sweep loop
    (threadless deployments get live control), daemons also run
    ``start()``'s background ticker. Disabled (the default), every hook
    is one attribute test and nothing here ever runs.
    """

    def __init__(self, instance, metrics=None, recorder=None):
        self.instance = instance
        self.metrics = metrics
        self.recorder = recorder
        beh = instance.conf.behaviors
        flag = getattr(beh, "autopilot", None)
        if flag is None:
            flag = os.environ.get("GUBER_AUTOPILOT", "0").lower() in (
                "1", "true", "yes", "on")
        self.enabled = bool(flag)

        self._lock = witness.make_lock("autopilot.state")
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_tick = 0.0
        self.ticks = 0
        self.moves = 0
        self.clamps = 0
        self.freezes = 0
        self.frozen_drops = 0
        self.frozen = False
        self.freeze_reason: Optional[str] = None
        self._freeze_until = 0.0
        # pipeline-pressure rate state (fill-stall delta per tick)
        self._prev_stalls: Optional[int] = None
        self._prev_stall_t = 0.0
        self._peer_cb = None

        states = {name: _KnobState(spec) for name, spec in KNOBS.items()}
        senses = {
            "admission": self._sense_admission,
            "hotkey": self._sense_hotkey,
            "capacity": self._sense_capacity,
            "pipeline": self._sense_pipeline,
        }
        self.controllers = []
        for reg in CONTROLLERS:
            knobs = {k: states[k] for k in reg["knobs"]}
            ctl = _Controller(reg, senses[reg["name"]], knobs)
            if ctl.name == "admission":
                # trip tracks the LIVE brownout fraction, clear half it
                ctl.thresholds = self._admission_thresholds  # type: ignore
            self.controllers.append(ctl)

        if self.enabled:
            # membership changes freeze actuation for a hold window even
            # when resharding is off (the peer flip itself reshuffles
            # ownership; moving knobs mid-flip double-perturbs)
            self._peer_cb = self._on_peers_change
            instance.on_peers_change(self._peer_cb)

    # ------------------------------------------------------------ knobs

    def _admission_thresholds(self) -> Tuple[float, float]:
        trip = float(getattr(self.instance.conf.behaviors,
                             "brownout_fraction", 0.75))
        return trip, trip * 0.5

    @property
    def interval_s(self) -> float:
        return max(float(getattr(self.instance.conf.behaviors,
                                 "autopilot_interval_s", 1.0)), 0.02)

    @property
    def dwell_s(self) -> float:
        return float(getattr(self.instance.conf.behaviors,
                             "autopilot_dwell_s", 5.0))

    @property
    def cooldown_s(self) -> float:
        return float(getattr(self.instance.conf.behaviors,
                             "autopilot_cooldown_s", 10.0))

    @property
    def freeze_hold_s(self) -> float:
        return float(getattr(self.instance.conf.behaviors,
                             "autopilot_freeze_hold_s", 5.0))

    def _read_knob(self, name: str) -> Optional[float]:
        inst = self.instance
        if name == "keyspace_interval_s":
            return float(inst.keyspace.interval_s)
        if name == "pipeline_depth":
            comb = inst.combiner
            if not (comb.pipelined and getattr(comb, "_depth_auto", False)):
                return None  # pinned depth is operator intent
            return float(comb.depth)
        return float(getattr(inst.conf.behaviors, name))

    def _write_knob(self, name: str, value: float) -> None:
        inst = self.instance
        if name == "keyspace_interval_s":
            inst.keyspace.interval_s = float(value)
        elif name == "pipeline_depth":
            inst.combiner.set_depth(int(value))
        elif name == "max_pending":
            setattr(inst.conf.behaviors, name, int(value))
        else:
            setattr(inst.conf.behaviors, name, float(value))

    # ----------------------------------------------------------- senses

    def _sense_admission(self) -> Optional[float]:
        adm = self.instance.admission
        if not adm.enabled:
            return None
        frac = adm.pending() / float(adm.max_pending)
        if self.instance.anomaly.active.get("shed_spike"):
            frac = max(frac, 1.0)
        return frac

    def _sense_hotkey(self) -> Optional[float]:
        if not self.instance.leases.enabled:
            return None
        rep = self.instance.keyspace.last_report()
        hm = (rep or {}).get("hit_mass") or {}
        top1 = hm.get("top1_share")
        return None if top1 is None else float(top1)

    def _sense_capacity(self) -> Optional[float]:
        ks = self.instance.keyspace
        if not ks.enabled:
            return None
        fc = ks.forecast()
        if not fc.get("projectable"):
            return 1.0 if self.instance.anomaly.active.get("capacity") else 0.0
        ttp = fc.get("time_to_pressure_s")
        if ttp is None:
            return 0.0
        horizon = self.instance.anomaly.capacity_horizon_s
        if ttp <= 0:
            return 2.0  # already past the pressure floor
        return min(horizon / float(ttp), 4.0)

    def _sense_pipeline(self) -> Optional[float]:
        comb = self.instance.combiner
        if not (comb.pipelined and getattr(comb, "_depth_auto", False)):
            return None
        now = time.monotonic()
        stalls = comb.stats.get("fill_stalls", 0)
        rate = 0.0
        if self._prev_stalls is not None and now > self._prev_stall_t:
            rate = (stalls - self._prev_stalls) / (now - self._prev_stall_t)
        self._prev_stalls, self._prev_stall_t = stalls, now
        v = rate / 20.0  # 20 fill-stalls/s saturates the signal at trip
        if self.instance.anomaly.active.get("profile_shift"):
            v = max(v, 1.0)
        return v

    # ------------------------------------------------------------- tick

    def maybe_tick(self) -> None:
        """Piggyback entry point (metric scrape, scenario sweep,
        health probe): run a tick when one is due. One attribute test
        when disabled; a non-blocking try-lock coalesces concurrent
        callers onto a single sweep."""
        if not self.enabled:
            return
        if time.monotonic() - self._last_tick < self.interval_s:
            return
        if not self._lock.acquire(blocking=False):
            return
        try:
            if time.monotonic() - self._last_tick >= self.interval_s:
                self._tick_locked(time.monotonic())
        finally:
            self._lock.release()

    def tick(self, now: Optional[float] = None) -> None:
        """Unconditional sweep (the daemon ticker and tests)."""
        if not self.enabled:
            return
        with background_of(self.instance, "autopilot.tick"), self._lock:
            self._tick_locked(time.monotonic() if now is None else now)

    def _tick_locked(self, now: float) -> None:
        self._last_tick = now
        self.ticks += 1
        frozen, reason = self._frozen(now)
        if frozen:
            self._enter_freeze(now, reason)
        else:
            self.frozen = False
            self.freeze_reason = None
            for ctl in self.controllers:
                try:
                    ctl.value = ctl.sense()
                except Exception:  # a broken sensor must never stop serving
                    log.exception("autopilot sense %s failed", ctl.name)
                    ctl.value = None
                ctl.decide(now, self.dwell_s)
                for kname, ks in ctl.knobs.items():
                    self._actuate(ctl, kname, ks, now)
        self._export_gauges()

    def _frozen(self, now: float) -> Tuple[bool, Optional[str]]:
        rm = self.instance.reshard
        if getattr(rm, "enabled", False) and getattr(rm, "active", False):
            return True, "reshard"
        if now < self._freeze_until:
            return True, "membership"
        return False, None

    def _enter_freeze(self, now: float, reason: Optional[str]) -> None:
        dropped = 0
        for ctl in self.controllers:
            if ctl.drop_intent():
                dropped += 1
        self.frozen_drops += dropped
        if not self.frozen:  # rising edge
            self.freezes += 1
            self._emit("autopilot.freeze", reason=reason,
                       dropped_intents=dropped)
            m = self.metrics
            if m is not None and hasattr(m, "autopilot_freezes"):
                m.autopilot_freezes.inc()
        self.frozen = True
        self.freeze_reason = reason

    def _on_peers_change(self, *_a, **_kw) -> None:
        # called from set_peers outside instance locks; stamping a
        # monotonic deadline is enough — the next tick observes it
        self._freeze_until = time.monotonic() + self.freeze_hold_s

    def _actuate(self, ctl: _Controller, kname: str, ks: _KnobState,
                 now: float) -> None:
        current = self._read_knob(kname)
        if current is None:
            return
        if ks.baseline is None:
            ks.baseline = current
        spec = ks.spec
        lo, hi = ks.band()
        mult = (spec.ceiling if ctl.side == "ceiling" else spec.floor) \
            if ctl.engaged else 1.0
        target = min(max(ks.baseline * mult, lo), hi)
        step = abs(ks.baseline) * spec.step
        if spec.integer:
            target = float(round(target))
            step = max(step, 1.0)
        if abs(target - current) < 1e-9:
            return
        if ks.last_move and now - ks.last_move < self.cooldown_s:
            return  # rate limit: ≤1 move per knob per cooldown
        proposed = current + step if target > current else current - step
        # never overshoot the target, never leave the declared band
        if target > current:
            proposed = min(proposed, target)
        else:
            proposed = max(proposed, target)
        clamped = min(max(proposed, lo), hi)
        if clamped != proposed:
            self.clamps += 1
            self._emit("autopilot.clamp", controller=ctl.name, knob=kname,
                       signal=ctl.signal, value=ctl.value,
                       proposed=proposed, clamped=clamped,
                       floor=lo, ceiling=hi)
            m = self.metrics
            if m is not None and hasattr(m, "autopilot_clamps"):
                m.autopilot_clamps.labels(
                    controller=ctl.name, knob=kname).inc()
        if spec.integer:
            clamped = float(round(clamped))
        if abs(clamped - current) < 1e-9:
            return  # rounding ate the step: don't burn the cooldown
        self._write_knob(kname, clamped)
        ks.last_move = now
        ks.moves += 1
        self.moves += 1
        event = {"t": now, "controller": ctl.name, "knob": kname,
                 "signal": ctl.signal, "value": ctl.value,
                 "old": current, "new": clamped,
                 "floor": lo, "ceiling": hi, "step": spec.step,
                 "engaged": ctl.engaged}
        ks.last_event = event
        self._emit("autopilot.move",
                   **{k: v for k, v in event.items() if k != "t"})
        m = self.metrics
        if m is not None and hasattr(m, "autopilot_moves"):
            m.autopilot_moves.labels(controller=ctl.name, knob=kname).inc()
        log.info("autopilot %s: %s %s -> %s (signal %s=%s)",
                 ctl.name, kname, current, clamped, ctl.signal, ctl.value)

    def _emit(self, kind: str, **fields) -> None:
        rec = self.recorder
        if rec is not None:
            rec.emit(kind, **fields)

    def _export_gauges(self) -> None:
        m = self.metrics
        if m is None or not hasattr(m, "autopilot_frozen"):
            return
        m.autopilot_frozen.set(1 if self.frozen else 0)
        for ctl in self.controllers:
            m.autopilot_engaged.labels(controller=ctl.name).set(
                1 if ctl.engaged else 0)
            for kname in ctl.knobs:
                cur = self._read_knob(kname)
                if cur is not None:
                    m.autopilot_knob.labels(knob=kname).set(cur)

    # ---------------------------------------------------------- ticker

    def start(self) -> None:
        """Background sweep ticker (daemons; harness clusters rely on
        maybe_tick piggybacks instead)."""
        if not self.enabled or self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="autopilot", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.tick()
            except Exception:
                log.exception("autopilot tick failed")

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=2.0)
            self._thread = None
        if self._peer_cb is not None:
            try:
                self.instance.off_peers_change(self._peer_cb)
            except Exception:
                pass
            self._peer_cb = None

    # ----------------------------------------------------------- debug

    def stats(self) -> dict:
        return {"ticks": self.ticks, "moves": self.moves,
                "clamps": self.clamps, "freezes": self.freezes,
                "frozen_drops": self.frozen_drops}

    def debug(self) -> dict:
        """The pinned `autopilot` section of /v1/debug/vars
        (schema v6, tests/test_debug_schema.py)."""
        now = time.monotonic()
        out = {
            "enabled": self.enabled,
            "frozen": self.frozen,
            "freeze_reason": self.freeze_reason,
            "interval_s": self.interval_s,
            "dwell_s": self.dwell_s,
            "cooldown_s": self.cooldown_s,
            "ticks": self.ticks,
            "moves": self.moves,
            "clamps": self.clamps,
            "freezes": self.freezes,
            "frozen_drops": self.frozen_drops,
            "controllers": {c.name: c.debug(now) for c in self.controllers},
        }
        return out

"""Flight recorder: a bounded ring of causal, structured events.

Metrics answer "how much"; traces answer "where did THIS request go";
neither answers "what sequence of state transitions led to the incident".
The recorder fills that gap Dapper-style: subsystems emit rare,
high-signal events — circuit open/close, admission brownout enter/exit,
lease grant/deny/fail-close, pipeline group cuts and fill stalls, wire
version flips, GLOBAL queue high-water — each stamped with monotonic
nanoseconds, wall time, and the active traceparent (obs/trace.py), so a
diagnostic bundle can interleave them with spans into one timeline.

Cost discipline: emissions sit on serving-adjacent paths, so the
recorder must be near-free. The ring is a ``deque(maxlen=...)`` (O(1)
append with eviction), the only lock guards the per-kind counters, and
``GUBER_FLIGHT_RECORDER=0`` turns ``emit`` into a single attribute test.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Dict, List, Optional

from gubernator_tpu.obs import witness
from gubernator_tpu.obs import trace

DEFAULT_CAPACITY = 4096


def default_enabled() -> bool:
    """GUBER_FLIGHT_RECORDER escape hatch (Go ParseBool values; default
    on — the recorder is the always-on black box, opting OUT is the
    deliberate act)."""
    raw = os.environ.get("GUBER_FLIGHT_RECORDER", "").strip().lower()
    if raw in ("0", "f", "false", "no", "off"):
        return False
    return True


class FlightRecorder:
    """Bounded, lock-cheap structured event ring.

    Events are plain dicts so the tail serializes straight into bundles:
    ``{"t_ns": monotonic, "wall": epoch seconds, "kind": "circuit.open",
    "trace_id": <active trace or None>, ...emitter fields}``. ``emit``
    never raises — observability must not break serving.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 enabled: Optional[bool] = None):
        self.enabled = default_enabled() if enabled is None else bool(enabled)
        self.capacity = max(int(capacity), 16)
        self._ring: "collections.deque[dict]" = collections.deque(
            maxlen=self.capacity)
        self._lock = witness.make_lock("events.ring")
        self.counts: Dict[str, int] = {}
        self.dropped = 0  # events emitted past a full ring (evictions)

    # -------------------------------------------------------------- emit

    def emit(self, kind: str, **fields) -> None:
        if not self.enabled:
            return
        try:
            span = trace.current()
            ev = {
                "t_ns": time.monotonic_ns(),
                "wall": time.time(),
                "kind": kind,
                "trace_id": span.trace_id if span is not None else None,
            }
            ev.update(fields)
            with self._lock:
                if len(self._ring) == self.capacity:
                    self.dropped += 1
                self._ring.append(ev)
                self.counts[kind] = self.counts.get(kind, 0) + 1
        except Exception:  # noqa: BLE001 — the recorder must never break serving
            pass

    # -------------------------------------------------------------- read

    def tail(self, n: int = 0, kind: str = "") -> List[dict]:
        """Newest-last snapshot; optionally the last `n` and/or one
        `kind` prefix (``kind="circuit"`` matches ``circuit.*``)."""
        with self._lock:
            out = list(self._ring)
        if kind:
            out = [e for e in out
                   if e["kind"] == kind or e["kind"].startswith(kind + ".")]
        if n > 0:
            out = out[-n:]
        return out

    def count(self, kind: str) -> int:
        with self._lock:
            return self.counts.get(kind, 0)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.counts.clear()
            self.dropped = 0

    def debug(self) -> dict:
        """The /v1/debug/vars "flight_recorder" section."""
        with self._lock:
            return {
                "enabled": self.enabled,
                "capacity": self.capacity,
                "size": len(self._ring),
                "dropped": self.dropped,
                "counts": dict(self.counts),
            }

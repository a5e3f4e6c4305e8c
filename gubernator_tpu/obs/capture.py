"""Traffic-shape capture: snapshot what the observability plane saw
into a versioned, replayable trace.

The capture is a pure READ of three surfaces the daemon already
maintains — the history ring (decision-rate curves), the keyspace
cartographer (popularity concentration + Zipf fit), and the flight
recorder (recent operational events) — assembled into one JSON
document. No new instrumentation runs on the serving path: the only
cost of a capture is the assembly itself.

A trace is replayable because its `derived` section reduces the raw
curves to exactly what a `ScenarioSpec` needs: piecewise rate segments
(decision deltas between ring samples) and a key-popularity model
(the cartographer's fitted Zipf exponent over its live key count).
`gubernator_tpu.scenarios.replay.trace_to_spec` performs that last
step client-side; fidelity tolerances are documented there and pinned
by tests/test_scenarios.py.
"""

from __future__ import annotations

import json
import time
from typing import List, Optional

TRACE_SCHEMA_VERSION = 1

# Rate curves flatter than this (decisions/s) are noise, not traffic —
# segments below it are dropped from the derived schedule.
MIN_SEGMENT_RATE_RPS = 0.5


def _rate_segments(samples: List[dict]) -> List[dict]:
    """Decision-rate curve from ring samples: each adjacent pair whose
    counters moved becomes one {duration_s, rate_rps} segment. The ring
    stores cumulative counters, so deltas are exact regardless of tick
    jitter."""
    segs: List[dict] = []
    for prev, cur in zip(samples, samples[1:]):
        dt = cur["t"] - prev["t"]
        if dt <= 0:
            continue
        rate = max(0.0, (cur.get("decisions", 0.0)
                         - prev.get("decisions", 0.0))) / dt
        over = max(0.0, (cur.get("over_limit", 0.0)
                         - prev.get("over_limit", 0.0))) / dt
        segs.append({"duration_s": round(dt, 3),
                     "rate_rps": round(rate, 3),
                     "over_limit_rps": round(over, 3)})
    return segs


def _key_model(keyspace_report: Optional[dict]) -> dict:
    """The cartographer's popularity fit as a generator-ready model.
    Falls back to a mild-skew default when the daemon has no harvest
    (cartography disabled or the table is empty)."""
    model = {"kind": "zipf", "n_keys": 1024, "exponent": 1.1,
             "source": "default"}
    if not keyspace_report:
        return model
    occ = (keyspace_report.get("occupancy") or {}).get("key_count")
    if occ:
        model["n_keys"] = max(1, int(occ))
    hm = keyspace_report.get("hit_mass") or {}
    expo = hm.get("zipf_exponent")
    if expo is not None:
        # the fit is a slope estimate; clamp to the generator's sane band
        model["exponent"] = max(0.0, min(3.0, float(expo)))
        model["source"] = "cartography"
    elif occ:
        model["source"] = "occupancy_only"
    return model


def capture_trace(instance, n_samples: int = 0, n_events: int = 256) -> dict:
    """Assemble one replayable trace from a live instance's obs
    surfaces. Read-only; never raises past a missing surface — a stub
    instance captures an empty (but schema-valid) trace."""
    t0 = time.perf_counter()
    history = getattr(instance, "history", None)
    keyspace = getattr(instance, "keyspace", None)
    recorder = getattr(instance, "recorder", None)

    samples = history.tail(n_samples) if history is not None else []
    ks_report = keyspace.report() if keyspace is not None else None
    events = recorder.tail(n_events) if recorder is not None else []

    segments = _rate_segments(samples)
    live = [s for s in segments if s["rate_rps"] >= MIN_SEGMENT_RATE_RPS]
    total_s = sum(s["duration_s"] for s in live)
    decided = sum(s["rate_rps"] * s["duration_s"] for s in live)
    over = sum(s["over_limit_rps"] * s["duration_s"] for s in live)

    trace = {
        "schema_version": TRACE_SCHEMA_VERSION,
        "captured_at": time.time(),
        "node": getattr(instance, "advertise_address", ""),
        "window": {
            "samples": len(samples),
            "span_s": round(samples[-1]["t"] - samples[0]["t"], 3)
            if len(samples) >= 2 else 0.0,
            "tick_s": getattr(history, "tick_s", None)
            if history is not None else None,
        },
        "history": {
            "segments": segments,
        },
        "keyspace": {
            "report": ks_report,
        },
        "events": {
            "tail": events,
            "counts": recorder.debug()["counts"]
            if recorder is not None else {},
        },
        "derived": {
            "segments": live,
            "active_s": round(total_s, 3),
            "mean_rate_rps": round(decided / total_s, 3) if total_s else 0.0,
            "peak_rate_rps": round(
                max((s["rate_rps"] for s in live), default=0.0), 3),
            "over_limit_share": round(over / decided, 6) if decided else 0.0,
            "key_model": _key_model(ks_report),
        },
    }
    trace["capture_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
    return trace


def endpoint_body(instance, n_samples: int = 0, n_events: int = 256) -> dict:
    """The /v1/debug/capture response — the trace itself, so an operator
    can `curl ... > trace.json` and replay it with scenario tooling."""
    return capture_trace(instance, n_samples=n_samples, n_events=n_events)


def save_trace(trace: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace, f, indent=1, sort_keys=True)
        f.write("\n")


def load_trace(path: str) -> dict:
    with open(path) as f:
        trace = json.load(f)
    ver = trace.get("schema_version")
    if ver != TRACE_SCHEMA_VERSION:
        raise ValueError(
            f"trace {path}: schema_version {ver!r} "
            f"(this build reads {TRACE_SCHEMA_VERSION})")
    return trace

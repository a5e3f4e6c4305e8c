"""The arithmetic of an open loop's window (PR 45): what the `step: window`
line and the `open.*` readers say beside the latency from the due instant.
Every array is one entry a call due in the window, all clients together."""

from __future__ import annotations

import numpy as np


def percentile(sorted_values, q: float) -> float:
    """Nearest-rank percentile of an ascending array."""
    k = max(int(np.ceil(q * len(sorted_values))) - 1, 0)
    return float(sorted_values[k])


def in_flight_max(due_ns, lat_ns) -> int:
    """The most calls due and not yet answered at one instant, over all
    clients (an answer at the instant another call is due leaves first)."""
    at = np.concatenate([due_ns, due_ns + lat_ns])
    step = np.concatenate([np.ones(len(due_ns), np.int64),
                           -np.ones(len(due_ns), np.int64)])
    order = np.lexsort((step, at))
    return int(np.cumsum(step[order]).max()) if len(at) else 0


def window(due_ns, lat_ns, lag_ns, win_start: float, seconds: float) -> dict:
    """A call's latency (`lat_ns`, answer - due) is its send lag (`lag_ns`,
    sent - due: the generator's, and the host's that holds it) plus
    sent -> answered (the daemon's, and the link's). Both parts, the
    window's slowest call and when it was due, and whether a backlog grew
    through the window (the median by fifth of the window, and the calls
    still out when it closed)."""
    ws, we = int(win_start * 1e9), int((win_start + seconds) * 1e9)
    lag = np.sort(lag_ns) / 1e6
    answer = np.sort(lat_ns - lag_ns) / 1e6
    slow, late = int(np.argmax(lat_ns)), int(np.argmax(lag_ns))
    fifth = np.minimum((due_ns - ws) * 5 // (we - ws), 4)
    return {
        "send_lag_ms": {"p50": percentile(lag, 0.5),
                        "p99": percentile(lag, 0.99), "max": float(lag[-1]),
                        "max_due_s": float(due_ns[late] - ws) / 1e9},
        "answer_ms": {"p50": percentile(answer, 0.5),
                      "p90": percentile(answer, 0.9),
                      "p99": percentile(answer, 0.99),
                      "max": float(answer[-1])},
        "in_flight_max": in_flight_max(due_ns, lat_ns),
        "in_flight_at_close": int((due_ns + lat_ns > we).sum()),
        "p50_by_fifth_ms": [
            float(np.median(lat_ns[fifth == i])) / 1e6 if (fifth == i).any()
            else None for i in range(5)],
        "slowest_call": {"ms": float(lat_ns[slow]) / 1e6,
                         "due_s": float(due_ns[slow] - ws) / 1e9,
                         "send_lag_ms": float(lag_ns[slow]) / 1e6},
    }

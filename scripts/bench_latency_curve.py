"""Device-time-vs-width curve for the latency story.

BASELINE.md's p99 < 2 ms target is a LATENCY-mode bar: a locally-attached
chip serving one flat-combining window synchronously. This script isolates
the ON-CHIP term of it: time a K-deep `lax.scan` of the decision kernel in
ONE dispatch, difference two depths, and the dispatch/link overhead
cancels:

    device_per_window(W) = (t(scan K2, W) - t(scan K1, W)) / (K2 - K1)

Every timed quantity is completion-forced (data-dependent scalar fetch).
The curve feeds DESIGN.md "Latency mode" and OPERATIONS.md's
window-width guidance: p99 on local hardware composes as
device_per_window + PCIe transfer (12 B/decision round trip, ~µs) +
local dispatch overhead (~100-300 µs PJRT launch).

Prints one JSON line: {"widths": {...}, "table_capacity": N, ...}.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TABLE_CAPACITY = 10_000_000
WIDTHS = (512, 1024, 2048, 4096, 8192)
REPS = 3  # per measurement; median-of-reps kills host-jitter outliers


def depths_for(width: int):
    """Differencing depths scaled so the K2-K1 device term (~1M decisions)
    dwarfs per-dispatch jitter at every width."""
    k2 = max(64, (1_000_000 + width - 1) // width)
    return max(8, k2 // 8), k2


def main() -> None:
    import jax
    import jax.numpy as jnp

    from gubernator_tpu.ops.decide import (
        decide_scan_packed_lean,
        lean_window,
        make_table,
    )
    from gubernator_tpu.utils.platform import donation_supported

    dargs = dict(donate_argnums=(0,)) if donation_supported() else {}
    step = jax.jit(decide_scan_packed_lean, **dargs)
    now = 1_700_000_000_000
    rng = np.random.RandomState(11)

    def force(x) -> int:
        return int(np.asarray(x[(0,) * x.ndim]))

    # ONE shared permutation; each window takes a disjoint slice — same
    # collision-free-window contract as rng.choice(replace=False) per
    # window, without paying a fresh 10M permutation per window
    perm = rng.permutation(TABLE_CAPACITY)
    perm_pos = [0]

    def windows(k: int, w: int):
        p = np.zeros((k, 9, w), np.int64)
        for i in range(k):
            if perm_pos[0] + w > TABLE_CAPACITY:
                perm_pos[0] = 0
            p[i, 0] = perm[perm_pos[0]:perm_pos[0] + w]
            perm_pos[0] += w
            p[i, 1] = 1
            p[i, 2] = rng.choice([100, 1000, 10000], w)
            p[i, 3] = 60_000
            p[i, 4] = rng.randint(0, 2, w)
        lanes, cfg = lean_window(p, TABLE_CAPACITY)
        return jnp.asarray(lanes), jnp.asarray(cfg)

    state = make_table(TABLE_CAPACITY)
    out = {"bench": "latency_curve", "table_capacity": TABLE_CAPACITY,
           "reps": REPS,
           "completion_barrier": "data-dependent fetch", "widths": {}}

    for w in WIDTHS:
        K1, K2 = depths_for(w)
        l1, cfg = windows(K1, w)
        l2, _ = windows(K2, w)
        # warm both shapes
        state, r = step(state, l1, cfg, now)
        force(r)
        state, r = step(state, l2, cfg, now)
        force(r)
        t1s, t2s = [], []
        for rep in range(REPS):
            t0 = time.perf_counter()
            state, r = step(state, l1, cfg, now + rep)
            force(r)
            t1s.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            state, r = step(state, l2, cfg, now + 100 + rep)
            force(r)
            t2s.append(time.perf_counter() - t0)
        t1 = float(np.median(t1s))
        t2 = float(np.median(t2s))
        dev_ms = max(t2 - t1, 0.0) / (K2 - K1) * 1e3
        out["widths"][str(w)] = {
            "scan_depths": [K1, K2],
            "device_ms_per_window": round(dev_ms, 4),
            "device_us_per_decision": round(dev_ms * 1e3 / w, 4),
            "device_decisions_per_sec": round(w / (dev_ms / 1e3), 1)
            if dev_ms > 0 else None,
            # local-chip p99 composition: on-chip + PCIe transfer of
            # 12 B/dec at >=10 GB/s + PJRT launch overhead
            "p99_ms_local_estimate": round(
                dev_ms + (12 * w) / 10e9 * 1e3 + 0.3, 3),
            "scan_k1_s": round(t1, 4), "scan_k2_s": round(t2, 4),
        }

    print(json.dumps({**out, "device": str(jax.devices()[0])}), flush=True)


if __name__ == "__main__":
    main()

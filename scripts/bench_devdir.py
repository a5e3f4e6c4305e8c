"""Device-directory vs host-directory serving, measured on the real device.

The device directory (models/devdir_engine.py) was graduated on a
measurement of a PROTOTYPE path, when the host path still staged
~72 B/decision wide.  Round 4's
interned i32[2] serving staging ships 8 B/decision on the HOST path too,
so the devdir's wire advantage is gone by construction — what remains is
the host-CPU question: keydir lookup+prep (~100 ns/item, GIL held in
parts) vs a C fnv batch alone (measured 89.8 ns/item on this host — the
string hashing both paths pay dominates either way).  This bench measures
both engines through the SAME front door (get_rate_limits), same widths,
same resident keyset, on whatever platform JAX gives (the attached chip;
CPU JAX under JAX_PLATFORMS=cpu), plus the host-side cost in isolation.
Every line names the platform it ran on.

Usage: python scripts/bench_devdir.py [--keys 1000000] [--width 4096]
       [--rounds 8]
Emits one JSON line per scenario.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _requests(names, start, count):
    from gubernator_tpu.types import RateLimitReq

    return [
        RateLimitReq(
            name="bench", unique_key=names[(start + i) % len(names)],
            hits=1, limit=1 << 30, duration=3_600_000,
        )
        for i in range(count)
    ]


def _seed(engine, names, width):
    for off in range(0, len(names), width):
        chunk = names[off:off + width]
        engine.get_rate_limits(_requests(chunk, 0, len(chunk)))


def _serve_rounds(engine, names, width, rounds, rng):
    """Sequential serving windows of `width` random resident keys;
    responses are materialized host-side every call (completion-forced
    by construction).  Returns (req/s, per-window seconds)."""
    # one warm call per width bucket so no timed window pays a compile
    engine.get_rate_limits(_requests(names, 0, width))
    t0 = time.perf_counter()
    n = 0
    for _ in range(rounds):
        start = int(rng.integers(0, len(names)))
        out = engine.get_rate_limits(_requests(names, start, width))
        n += len(out)
        assert out[0].error == ""
    dt = time.perf_counter() - t0
    return n / dt, dt / rounds


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=1_000_000)
    ap.add_argument("--width", type=int, default=4096)
    ap.add_argument("--rounds", type=int, default=8)
    args = ap.parse_args()
    if args.keys < args.width:
        ap.error("--keys must be >= --width (duplicate keys in one window "
                 "decide in sequential rounds and would skew req/s)")

    import jax

    from gubernator_tpu import native
    from gubernator_tpu.models.devdir_engine import DevDirEngine
    from gubernator_tpu.models.engine import Engine

    platform = jax.devices()[0].platform
    cap = 1 << max(20, (args.keys * 2 - 1).bit_length())
    names = [f"k:{i:012d}" for i in range(args.keys)]
    rng = np.random.default_rng(7)

    rows = []
    for label, ctor in (("hostdir", Engine), ("devdir", DevDirEngine)):
        eng = ctor(capacity=cap, min_width=64, max_width=8192)
        t0 = time.perf_counter()
        _seed(eng, names, 8192)
        seed_s = time.perf_counter() - t0
        rate, per_window = _serve_rounds(
            eng, names, args.width, args.rounds, rng)
        rows.append({
            "scenario": f"devdir_bench_{label}",
            "platform": platform,
            "resident_keys": args.keys,
            "width": args.width,
            "req_per_sec": round(rate, 1),
            "window_ms": round(per_window * 1e3, 2),
            "seed_s": round(seed_s, 1),
        })
        print(json.dumps(rows[-1]), flush=True)
        del eng

    # host-side per-item cost in isolation: what each directory charges
    # the serving CPU before any dispatch
    native.load_library()
    key_sample = names[: args.width]
    t0 = time.perf_counter()
    reps = 200
    for _ in range(reps):
        native.fingerprint_batch(key_sample)
    fnv_ns = (time.perf_counter() - t0) / (reps * args.width) * 1e9
    print(json.dumps({
        "scenario": "devdir_bench_host_cost",
        "fnv_hash_ns_per_item": round(fnv_ns, 1),
        "note": "hostdir path adds directory lookup+pin (~100 ns/item, "
                "measured in DESIGN.md 'Native host tier'); devdir ships "
                "only this hash",
    }), flush=True)


if __name__ == "__main__":
    main()

"""Snapshot/restore at production scale (VERDICT r3 item 6).

10M keys through the STREAMED paths end to end:

  seed      synthetic BucketSnapshot generator -> Engine.load_snapshot
            (chunked directory insert + row inject; nothing materialized)
  save      Engine.snapshot_stream -> FileLoader.save (slab row fetches,
            vectorized filter, rows stream straight into the file)
  restore   FileLoader.load (streamed JSONL) -> fresh Engine.load_snapshot
  verify    spot peeks through the public API

Reports seconds per phase, snapshot file size, peak host RSS and the
platform it ran on. Runs on the ambient device; --platform=cpu pins JAX to
the CPU to look at the HOST persistence path alone.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--keys", type=int, default=10_000_000)
    ap.add_argument("--path", default="/tmp/guber_snapshot_bench.snap")
    ap.add_argument("--platform", default="default",
                    choices=["cpu", "default"])
    ap.add_argument("--format", default="binary",
                    choices=["binary", "jsonl"])
    args = ap.parse_args()

    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    print(json.dumps({"platform": jax.devices()[0].platform,
                      "device_kind": jax.devices()[0].device_kind}),
          flush=True)

    from gubernator_tpu.models.engine import Engine
    from gubernator_tpu.store import (
        BinarySnapshotLoader,
        BucketSnapshot,
        FileLoader,
    )

    N = args.keys
    NOW = 4_000_000_000_000  # far future: nothing expires mid-bench

    def synthetic():
        for i in range(N):
            yield BucketSnapshot(
                key=f"sb_{i}", algo=i & 1, limit=100, remaining=100 - (i % 7),
                duration=3_600_000, stamp=NOW - 1000, expire_at=NOW,
                status=0)

    out = {"bench": "snapshot_10m", "keys": N, "format": args.format,
           "rss0_mb": round(rss_mb(), 1)}

    eng = Engine(capacity=N, min_width=64, max_width=8192)
    t0 = time.perf_counter()
    n = eng.load_snapshot(synthetic())
    out["seed_s"] = round(time.perf_counter() - t0, 2)
    assert n == N

    if args.format == "binary":
        loader = BinarySnapshotLoader(args.path)
        t0 = time.perf_counter()
        loader.save_slabs(eng.snapshot_slabs())
    else:
        loader = FileLoader(args.path)
        t0 = time.perf_counter()
        loader.save(eng.snapshot_stream())
    out["save_s"] = round(time.perf_counter() - t0, 2)
    out["file_mb"] = round(os.path.getsize(args.path) / 1e6, 1)
    out["rss_after_save_mb"] = round(rss_mb(), 1)
    del eng

    eng2 = Engine(capacity=N, min_width=64, max_width=8192)
    t0 = time.perf_counter()
    if args.format == "binary":
        n2 = eng2.load_snapshot_slabs(loader.load_slabs())
    else:
        n2 = eng2.load_snapshot(loader.load())
    out["restore_s"] = round(time.perf_counter() - t0, 2)
    assert n2 == N, (n2, N)

    # spot-verify through the public API
    from gubernator_tpu.types import RateLimitReq

    for i in (0, N // 2, N - 1):
        key = f"sb_{i}"
        r = eng2.get_rate_limits([RateLimitReq(
            name="sb", unique_key=key[3:], hits=0, limit=100,
            duration=3_600_000, algorithm=i & 1)],  # match the row's
            now_ms=NOW - 500)[0]  # algo: a mismatch resets the bucket
        assert r.remaining == 100 - (i % 7), (key, r)
    out["peak_rss_mb"] = round(rss_mb(), 1)
    os.unlink(args.path)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

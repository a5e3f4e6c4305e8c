"""Skewed-traffic tests: Zipf-head batches under the EXISTING machinery.

The lease tier (tests/test_leases.py) is the cross-host answer to hot
keys; these tests pin down the single-host story it builds on — that a
Zipf-1.1 batch is already cheap at the owner, because duplicate keys in
one window collapse into rounds ("d duplicates = d rounds", models/prep.py)
and concurrent hot-key callers collapse into shared combiner windows
(service/combiner.py). Both properties are asserted bit-exactly against
the serial path, with simulated time so no sleeps are needed.
"""

import threading

import numpy as np
import pytest

from gubernator_tpu.models.engine import Engine
from gubernator_tpu.service.combiner import BackendCombiner
from gubernator_tpu.types import Algorithm, RateLimitReq, Status

NOW = 1_700_000_000_000


def zipf_keys(n, n_keys, seed=7, a=1.1):
    """Zipf-1.1 key indices, folded into n_keys distinct keys,
    pinned-seed."""
    rng = np.random.RandomState(seed)
    return [int(k) % n_keys for k in rng.zipf(a, size=n)]


def req(key, hits=1, limit=10_000, duration=60_000):
    return RateLimitReq(name="skew", unique_key=str(key), hits=hits,
                        limit=limit, duration=duration,
                        algorithm=Algorithm.TOKEN_BUCKET)


class TestZipfRounds:
    def test_duplicate_rounds_collapse(self):
        """One Zipf-1.1 window costs max-multiplicity rounds, not one
        round per request: the d-duplicates-d-rounds contract is what
        keeps the owner's dispatch count flat under head-heavy skew."""
        eng = Engine(capacity=512, min_width=32, max_width=256)
        n = 256
        keys = zipf_keys(n, n_keys=32)
        reqs = [req(k) for k in keys]
        multiplicity = max(np.bincount(keys))
        assert multiplicity > 8  # the head is actually hot at a=1.1

        r0 = eng.stats.rounds
        resps = eng.get_rate_limits(reqs, now_ms=NOW)
        rounds = eng.stats.rounds - r0
        assert all(r.status == Status.UNDER_LIMIT for r in resps)
        assert rounds == multiplicity
        assert rounds < n // 4  # collapsed, not serialized

    def test_zipf_batch_vs_serial_bit_exact(self):
        """The collapsed batch is BIT-identical to one-request-at-a-time
        serial application: occurrence k of a duplicate key lands in
        round k, so ordering (and thus every remaining/status value)
        matches the serial replay exactly."""
        n = 192
        keys = zipf_keys(n, n_keys=24, seed=11)
        # mixed hit sizes so remaining trajectories are distinctive, and a
        # tight limit so the head crosses OVER_LIMIT mid-batch
        reqs = [req(k, hits=1 + (i % 3), limit=40) for i, k in enumerate(keys)]

        batched = Engine(capacity=512, min_width=32, max_width=256)
        serial = Engine(capacity=512, min_width=32, max_width=256)
        out_b = batched.get_rate_limits(reqs, now_ms=NOW)
        out_s = [serial.get_rate_limits([r], now_ms=NOW)[0] for r in reqs]

        assert any(r.status == Status.OVER_LIMIT for r in out_s)
        for i, (b, s) in enumerate(zip(out_b, out_s)):
            assert (b.status, b.limit, b.remaining, b.reset_time) == \
                (s.status, s.limit, s.remaining, s.reset_time), f"index {i}"


class TestCombinerHotKey:
    def test_concurrent_hot_key_shares_windows(self):
        """A thundering herd on ONE key collapses into shared combiner
        windows: far fewer engine batches than callers, with every hit
        still accounted (remaining == limit - callers)."""
        eng = Engine(capacity=256, min_width=32, max_width=256)
        eng.warmup()
        comb = BackendCombiner(eng)
        n_callers = 64
        start = threading.Barrier(n_callers)
        errs = []

        def caller():
            try:
                start.wait(timeout=10)
                r = comb.submit([req("hot", limit=1000)])[0]
                assert r.status == Status.UNDER_LIMIT
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        b0 = eng.stats.batches
        threads = [threading.Thread(target=caller) for _ in range(n_callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        comb.close()
        assert not errs
        batches = eng.stats.batches - b0
        assert batches < n_callers // 2, \
            f"micro-batching did not collapse: {batches} batches"
        peek = RateLimitReq(name="skew", unique_key="hot", hits=0,
                            limit=1000, duration=60_000,
                            algorithm=Algorithm.TOKEN_BUCKET)
        final = eng.get_rate_limits([peek])[0]
        assert final.remaining == 1000 - n_callers


class TestDeviceHitCounter:
    def test_col7_accumulates_attempted_hits(self):
        """Table column 7 counts ATTEMPTED hits — admitted and rejected
        both — giving lease detection a device-resident per-key rate with
        zero extra dispatches (ops/decide.py)."""
        eng = Engine(capacity=128, min_width=32, max_width=256)
        eng.get_rate_limits([req("c7", hits=4, limit=10)], now_ms=NOW)
        eng.get_rate_limits([req("c7", hits=3, limit=10)], now_ms=NOW + 1)
        # over-request: rejected without deducting, but still ATTEMPTED
        over = eng.get_rate_limits([req("c7", hits=9, limit=10)],
                                   now_ms=NOW + 2)[0]
        assert over.status == Status.OVER_LIMIT
        counts = eng.device_hit_counts(["skew_c7"])
        assert counts == {"skew_c7": 4 + 3 + 9}

    def test_col7_invisible_in_responses(self):
        """The counter never leaks into decision outputs: an engine with a
        hot-key tracker attached answers bit-identically to one without."""
        from gubernator_tpu.service.leases import HotKeyTracker

        tracked = Engine(capacity=64, min_width=32, max_width=256)
        tracked.hot_tracker = HotKeyTracker(
            capacity=64, rate_threshold=1.0, window_s=3600.0,
            resolver=tracked.resolve_slots)
        plain = Engine(capacity=64, min_width=32, max_width=256)
        seq = [req("x", hits=2, limit=9), req("y", hits=9, limit=9),
               req("x", hits=9, limit=9), req("y", hits=1, limit=9)]
        for i, r in enumerate(seq):
            a = tracked.get_rate_limits([r], now_ms=NOW + i)[0]
            b = plain.get_rate_limits([r], now_ms=NOW + i)[0]
            assert (a.status, a.limit, a.remaining, a.reset_time) == \
                (b.status, b.limit, b.remaining, b.reset_time)

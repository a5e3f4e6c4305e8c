"""Native lone-request fast path (VERDICT r2 item 6).

keydir.cpp decide_one answers NO_BATCHING singles against a
directory-resident row mirror — no kernel dispatch, no GIL — with the
oracle semantics (ops/oracle.py). The correctness contract is
reconciliation: a mirror decision must be indistinguishable from a kernel
decision, including when batch windows interleave (dirty mirrors flush
into the device table through the prep inject rows before the window
decides).
"""

import numpy as np
import pytest

from gubernator_tpu.models.engine import Engine
from gubernator_tpu.types import Algorithm, Behavior, RateLimitReq, Status

NOW = 1_700_000_000_000


def _req(key, hits=1, limit=10, duration=60_000, behavior=0,
         algo=Algorithm.TOKEN_BUCKET):
    return RateLimitReq(name="ns", unique_key=key, hits=hits, limit=limit,
                        duration=duration, algorithm=algo, behavior=behavior)


def _mk():
    e = Engine(capacity=1024, min_width=16, max_width=128)
    e.warmup()
    return e


def native_or_kernel(eng, req, now):
    """The serving discipline: native first, kernel + seed on miss."""
    r = eng.decide_native_single(req, now_ms=now)
    if r is not None:
        return r, True
    r = eng.get_rate_limits([req], now_ms=now)[0]
    eng.seed_mirror(req.hash_key())
    return r, False


class TestNativeSingleDifferential:
    def test_random_stream_matches_kernel(self):
        """Twin engines: one all-kernel, one native-first with kernel
        seeding and interleaved batch windows. Bit-identical responses."""
        a, b = _mk(), _mk()
        rng = np.random.default_rng(23)
        keys = [f"k{i}" for i in range(6)]
        now = NOW
        native_hits = 0
        for step in range(300):
            now += int(rng.choice([0, 1, 50, 997, 10_000, 3_600_000]))
            if rng.random() < 0.15:
                # a batch window forces mirror reconciliation
                batch = [_req(k, hits=int(rng.integers(0, 3)))
                         for k in rng.choice(keys, 4, replace=False)]
                wa = a.get_rate_limits(batch, now_ms=now)
                wb = b.get_rate_limits(batch, now_ms=now)
                assert wa == wb, (step, batch)
                continue
            algo = (Algorithm.TOKEN_BUCKET if rng.random() < 0.7
                    else Algorithm.LEAKY_BUCKET)
            beh = (int(Behavior.RESET_REMAINING)
                   if rng.random() < 0.07 else 0)
            req = _req(str(rng.choice(keys)),
                       hits=int(rng.integers(0, 4)),
                       limit=int(rng.choice([3, 10, 25])),
                       duration=int(rng.choice([500, 60_000])),
                       behavior=beh, algo=algo)
            want = a.get_rate_limits([req], now_ms=now)[0]
            got, was_native = native_or_kernel(b, req, now)
            native_hits += was_native
            assert (got.status, got.limit, got.remaining, got.reset_time) \
                == (want.status, want.limit, want.remaining,
                    want.reset_time), (step, req, got, want)
        assert native_hits > 25  # the fast path actually served traffic
        assert b.stats.native_singles == native_hits

    def test_mirror_reconciles_into_batch_window(self):
        """Hits taken natively must be visible to the next kernel window
        (the dirty mirror injects before the window decides)."""
        eng = _mk()
        eng.get_rate_limits([_req("rec", hits=2, limit=10)], now_ms=NOW)
        assert eng.seed_mirror("ns_rec")
        for i in range(3):  # 3 native hits: remaining 7,6,5
            r = eng.decide_native_single(_req("rec", hits=1), now_ms=NOW + i)
            assert r is not None
        assert r.remaining == 5
        # kernel window (batch of 2 keys) sees the natively-updated row
        out = eng.get_rate_limits(
            [_req("rec", hits=1), _req("other", hits=1)], now_ms=NOW + 10)
        assert out[0].remaining == 4
        # and the mirror is invalidated until re-seeded
        assert eng.decide_native_single(_req("rec"), now_ms=NOW + 11) is None

    def test_snapshot_flushes_dirty_mirrors(self):
        eng = _mk()
        eng.get_rate_limits([_req("snap", hits=1, limit=10)], now_ms=NOW)
        eng.seed_mirror("ns_snap")
        eng.decide_native_single(_req("snap", hits=4), now_ms=NOW + 1)
        # include_expired: the test clock is fixed epoch, snapshot's
        # liveness filter runs on the real wall clock
        rows = {s.key: s for s in eng.snapshot(include_expired=True)}
        assert rows["ns_snap"].remaining == 5  # 10 - 1 - 4
        # flush cleared the dirty flag; a second snapshot agrees
        rows2 = {s.key: s for s in eng.snapshot(include_expired=True)}
        assert rows2["ns_snap"].remaining == 5

    def test_reset_remaining_deletes_bucket_natively(self):
        eng = _mk()
        eng.get_rate_limits([_req("rr", hits=7, limit=10)], now_ms=NOW)
        eng.seed_mirror("ns_rr")
        r = eng.decide_native_single(
            _req("rr", behavior=int(Behavior.RESET_REMAINING)),
            now_ms=NOW + 1)
        assert r is not None and r.remaining == 10
        # the deletion reconciles: the next kernel touch sees a fresh bucket
        out = eng.get_rate_limits([_req("rr", hits=1, limit=10)],
                                  now_ms=NOW + 2)[0]
        assert out.remaining == 9

    def test_masked_behaviors_and_store_miss(self):
        eng = _mk()
        eng.get_rate_limits([_req("msk", hits=1)], now_ms=NOW)
        eng.seed_mirror("ns_msk")
        assert eng.decide_native_single(
            _req("msk", behavior=int(Behavior.GLOBAL)), now_ms=NOW) is None
        assert eng.decide_native_single(
            _req("msk", behavior=int(Behavior.DURATION_IS_GREGORIAN)),
            now_ms=NOW) is None
        # expired mirror is a miss (the kernel path recreates)
        assert eng.decide_native_single(
            _req("msk"), now_ms=NOW + 120_000) is None

    def test_expiry_and_algo_switch_fall_back(self):
        eng = _mk()
        eng.get_rate_limits([_req("sw", hits=1)], now_ms=NOW)
        eng.seed_mirror("ns_sw")
        # algorithm switch: the mirror can't serve it (kernel semantics
        # discard the row); must miss
        assert eng.decide_native_single(
            _req("sw", algo=Algorithm.LEAKY_BUCKET), now_ms=NOW + 1) is None


class TestPeerlinkNativeHop:
    def test_lone_hop_decides_in_io_thread(self):
        """The full loop: first lone hop misses (kernel path + seed), the
        following ones are answered by the C++ IO thread — no Python
        worker — and stay consistent with kernel windows afterwards."""
        from gubernator_tpu.service.config import InstanceConfig
        from gubernator_tpu.service.instance import Instance
        from gubernator_tpu.service.peerlink import (
            METHOD_GET_PEER_RATE_LIMITS,
            PeerLinkClient,
            PeerLinkService,
        )

        eng = _mk()
        inst = Instance(InstanceConfig(backend=eng),
                        advertise_address="self")
        svc = PeerLinkService(inst, port=0)
        cli = PeerLinkClient(f"127.0.0.1:{svc.port}")
        try:
            assert svc._seed_engine is eng
            call = lambda **kw: cli.call(
                METHOD_GET_PEER_RATE_LIMITS,
                [_req("hot", limit=100, **kw)], 5.0)[0]
            r1 = call()  # miss: Python path, then seed
            assert r1.remaining == 99
            assert svc.native_hits() == 0
            r2, r3 = call(), call()  # native, in the IO thread
            assert (r2.remaining, r3.remaining) == (98, 97)
            assert svc.native_hits() == 2
            assert eng.stats.batches == 1  # no further Python windows
            # a kernel window reconciles the natively-taken hits
            out = eng.get_rate_limits(
                [_req("hot", limit=100), _req("cold", limit=100)])
            assert out[0].remaining == 96
            # ...and invalidates the mirror: next hop re-misses + re-seeds
            r4 = call()
            assert r4.remaining == 95
            r5 = call()
            assert r5.remaining == 94
            assert svc.native_hits() == 3
        finally:
            cli.close()
            svc.close()
            inst.close()

    def test_lone_hop_latency_budget(self):
        """Loopback lone-hop latency through the native path. The <100 µs
        target assumes a deployment-shaped host; this rig is 1 CPU core
        shared by client and server, so assert a loose bound; no cell of
        the benchmark measures the lone hop between peers yet."""
        import time as _t

        from gubernator_tpu.service.config import InstanceConfig
        from gubernator_tpu.service.instance import Instance
        from gubernator_tpu.service.peerlink import (
            METHOD_GET_PEER_RATE_LIMITS,
            PeerLinkClient,
            PeerLinkService,
        )

        eng = _mk()
        inst = Instance(InstanceConfig(backend=eng),
                        advertise_address="self")
        svc = PeerLinkService(inst, port=0)
        cli = PeerLinkClient(f"127.0.0.1:{svc.port}")
        try:
            req = [_req("lat", limit=10**9)]
            cli.call(METHOD_GET_PEER_RATE_LIMITS, req, 5.0)  # seed
            lats = []
            for _ in range(300):
                t0 = _t.perf_counter()
                cli.call(METHOD_GET_PEER_RATE_LIMITS, req, 5.0)
                lats.append(_t.perf_counter() - t0)
            assert svc.native_hits() >= 290
            lats.sort()
            p50 = lats[len(lats) // 2]
            assert p50 < 0.002, f"native lone-hop p50 {p50*1e6:.0f}us"
        finally:
            cli.close()
            svc.close()
            inst.close()

"""Depth-N pipelined columnar wire path (the zero-object twin of
tests/test_pipeline.py).

The correctness bar from ISSUE 3: the pipelined columnar owner path
(models/engine.py launch_columnar_windows -> service/peerlink.py
_columnar_chunk) must be BIT-IDENTICAL to the lock-step columnar path
AND to the request-object path — including leftover demotions (invalid,
gregorian, GLOBAL), the group-cut barrier, over-commit error fill, and a
clean drain on service close.
"""

import threading

import numpy as np
import pytest

from gubernator_tpu import native
from gubernator_tpu.models.engine import Engine
from gubernator_tpu.models.prep import bucket_splits, bucket_width
from gubernator_tpu.types import Algorithm, Behavior, RateLimitReq

NOW = 1_700_000_000_000
SLOW = (int(Behavior.DURATION_IS_GREGORIAN) | int(Behavior.GLOBAL)
        | int(Behavior.MULTI_REGION))


def cols_from(reqs):
    """The peerlink wire layout for one sub-window, as a launch tuple."""
    names = [r.name.encode() for r in reqs]
    ukeys = [r.unique_key.encode() for r in reqs]
    keys = b"".join(a + b for a, b in zip(names, ukeys))
    off = np.zeros(len(reqs) + 1, np.int32)
    np.cumsum([len(a) + len(b) for a, b in zip(names, ukeys)],
              out=off[1:])
    return (len(reqs), keys, off,
            np.array([len(a) for a in names], np.int32),
            np.array([r.hits for r in reqs], np.int64),
            np.array([r.limit for r in reqs], np.int64),
            np.array([r.duration for r in reqs], np.int64),
            np.array([int(r.algorithm) for r in reqs], np.int32),
            np.array([int(r.behavior) for r in reqs], np.int32))


def _engine(max_width=16):
    eng = Engine(capacity=2048, min_width=8, max_width=max_width)
    if not eng.supports_columnar():
        pytest.skip("native columnar prep unavailable")
    return eng


def _outs(n):
    return (np.zeros(n, np.int32), np.zeros(n, np.int64),
            np.zeros(n, np.int64), np.zeros(n, np.int64))


def run_lockstep(eng, reqs, now_ms):
    """The pre-pipeline serving loop: complete sub-window i before
    submitting i+1, leftovers through the object path per sub-window."""
    st, li, re, rs = _outs(len(reqs))
    s0 = 0
    for ln in bucket_splits(len(reqs), eng.min_width, eng.max_width):
        s1 = s0 + ln
        c = cols_from(reqs[s0:s1])
        h = eng.submit_columnar(*c, SLOW, now_ms=now_ms)
        assert h is not None
        left = eng.complete_columnar(h, st[s0:s1], li[s0:s1], re[s0:s1],
                                     rs[s0:s1])
        for i in left.tolist():
            r = eng.get_rate_limits([reqs[s0 + i]], now_ms=now_ms)[0]
            st[s0 + i], li[s0 + i], re[s0 + i], rs[s0 + i] = (
                r.status, r.limit, r.remaining, r.reset_time)
        s0 = s1
    return st, li, re, rs


def run_pipelined(eng, reqs, now_ms, depth=3, scan=4, staging=None):
    """The peerlink pipelined loop distilled: scan-group launches with
    `depth` in flight, drain in dispatch order, barrier (drain ALL +
    retire leftovers through the object path) on any group cut."""
    import collections

    st, li, re, rs = _outs(len(reqs))
    spans = []
    s0 = 0
    for ln in bucket_splits(len(reqs), eng.min_width, eng.max_width):
        spans.append((s0, s0 + ln))
        s0 += ln
    if staging is None:
        staging = [dict() for _ in range(depth + 2)]
    inflight = collections.deque()
    stats = {"groups": 0, "cuts": 0, "max_inflight": 0}
    wi = 0
    seq = 0

    def drain_one():
        h, gspans = inflight.popleft()
        outs = [(st[a:b], li[a:b], re[a:b], rs[a:b]) for a, b in gspans]
        for (a, _b), left in zip(gspans,
                                 eng.collect_columnar_windows(h, outs)):
            for i in left.tolist():
                r = eng.get_rate_limits([reqs[a + i]], now_ms=now_ms)[0]
                st[a + i], li[a + i], re[a + i], rs[a + i] = (
                    r.status, r.limit, r.remaining, r.reset_time)
        return h[1]

    while wi < len(spans) or inflight:
        barrier = False
        while wi < len(spans) and len(inflight) < depth:
            gspans = spans[wi:wi + scan]
            wins = [cols_from(reqs[a:b]) for a, b in gspans]
            h = eng.launch_columnar_windows(
                wins, SLOW, now_ms=now_ms,
                staging=staging[seq % len(staging)])
            assert h is not None
            seq += 1
            consumed = len(h[0])
            assert consumed > 0 or h[1] is not None
            wi += consumed
            inflight.append((h, gspans[:consumed]))
            stats["groups"] += 1
            stats["max_inflight"] = max(stats["max_inflight"],
                                        len(inflight))
            cut = (consumed < len(gspans)
                   or (consumed and len(h[0][-1][-1])))
            if h[1] is not None:
                raise RuntimeError(h[1])
            if cut:
                stats["cuts"] += 1
                barrier = True
                break
        if inflight:
            if barrier or wi >= len(spans):
                while inflight:
                    drain_one()
            else:
                drain_one()
    return (st, li, re, rs), stats


def _random_reqs(rng, n, n_keys=25):
    reqs = []
    for _ in range(n):
        kind = rng.random()
        beh = 0
        duration = 60_000
        key = f"k{rng.integers(0, n_keys)}"
        if kind < 0.05:
            beh = int(Behavior.DURATION_IS_GREGORIAN)
            duration = int(rng.integers(0, 2))
            key = f"g{rng.integers(0, 3)}"
        elif kind < 0.08:
            key = ""  # invalid -> error lane via the object tail
        elif kind < 0.12:
            beh = int(Behavior.RESET_REMAINING)
        reqs.append(RateLimitReq(
            name="cp", unique_key=key, hits=int(rng.integers(0, 3)),
            limit=40, duration=duration,
            algorithm=(Algorithm.TOKEN_BUCKET if rng.random() < .7
                       else Algorithm.LEAKY_BUCKET),
            behavior=beh))
    return reqs


class TestPipelinedColumnarDifferential:
    def test_random_workload_bit_exact_three_ways(self):
        """Random chunks (duplicates, gregorian, invalid, both
        algorithms) through the object path, the lock-step columnar
        path, and the pipelined columnar path on triplet engines must
        agree on every field."""
        obj = _engine()
        lock = _engine()
        pipe = _engine()
        staging = [dict() for _ in range(5)]
        rng = np.random.default_rng(17)
        for it in range(12):
            reqs = _random_reqs(rng, int(rng.integers(20, 120)))
            now = NOW + it * 500
            want = obj.get_rate_limits(reqs, now_ms=now)
            lk = run_lockstep(lock, reqs, now)
            (st, li, re, rs), _stats = run_pipelined(
                pipe, reqs, now, depth=3, scan=4, staging=staging)
            for i, w in enumerate(want):
                w_t = (w.status, w.limit, w.remaining, w.reset_time)
                assert (lk[0][i], lk[1][i], lk[2][i], lk[3][i]) == w_t, \
                    (it, i, reqs[i], "lockstep")
                assert (st[i], li[i], re[i], rs[i]) == w_t, \
                    (it, i, reqs[i], "pipelined")

    def test_duplicate_key_hammer_bit_exact(self):
        """Every sub-window hammers one key: the group-cut barrier fires
        constantly and per-key sequential order must still hold exactly
        (remaining counts down 1:1 with wire order)."""
        pipe = _engine()
        reqs = [RateLimitReq(name="cp", unique_key="hot", hits=1,
                             limit=1000, duration=60_000)
                for _ in range(96)]
        (st, _li, re, _rs), stats = run_pipelined(pipe, reqs, NOW,
                                                  depth=4, scan=4)
        assert re.tolist() == list(range(999, 999 - 96, -1))
        assert (st == 0).all()
        assert stats["cuts"] > 0  # in-window duplicates forced barriers

    def test_distinct_keys_fill_the_pipeline(self):
        """The common serving shape (distinct keys) never cuts: groups
        coalesce to `scan` windows and `depth` launches ride in
        flight."""
        pipe = _engine()
        reqs = [RateLimitReq(name="cp", unique_key=f"d{i}", hits=1,
                             limit=10, duration=60_000)
                for i in range(256)]
        (st, _li, re, _rs), stats = run_pipelined(pipe, reqs, NOW,
                                                  depth=3, scan=4)
        assert (st == 0).all() and (re == 9).all()
        assert stats["cuts"] == 0
        assert stats["max_inflight"] == 3
        assert stats["groups"] == 4  # 16 windows / scan 4

    def test_group_cut_never_dispatches_unprepped_windows(self):
        """A cut at window m of a K-window group must not ship the
        not-yet-prepped staging rows — zeroed rows are live slot-0
        lanes, which would corrupt the first inserted key's row
        (the object-path pipeline's hazard, proven for the columnar
        twin)."""
        eng = _engine()  # max_width 16
        wins_reqs = [[RateLimitReq(name="s", unique_key=f"w{w}k{i}",
                                   hits=1, limit=100, duration=60_000)
                      for i in range(16)] for w in range(8)]
        # window 4 ends with an in-window duplicate -> cut at m=5
        wins_reqs[4][15] = RateLimitReq(name="s", unique_key="w4k0",
                                        hits=1, limit=100,
                                        duration=60_000)
        h = eng.launch_columnar_windows(
            [cols_from(rs) for rs in wins_reqs], SLOW, now_ms=NOW)
        assert h is not None and len(h[0]) == 5 and h[1] is None
        outs = [_outs(16) for _ in range(5)]
        lefts = eng.collect_columnar_windows(h, outs)
        assert [len(l) for l in lefts] == [0, 0, 0, 0, 1]
        assert outs[0][2].tolist() == [99] * 16
        assert outs[4][2][:15].tolist() == [99] * 15
        # slot 0 ("w0k0") must hold exactly one hit of state
        after = eng.get_rate_limits(
            [RateLimitReq(name="s", unique_key="w0k0", hits=1, limit=100,
                          duration=60_000)], now_ms=NOW)
        assert after[0].remaining == 98

    def test_over_commit_dispatches_prefix_and_reports(self):
        """Over-commit mid-group: the windows prepped before the failure
        still dispatch (their directory commits reached the device) and
        the handle carries the error for the caller's fill. Genuine
        over-commit is unreachable on a well-formed engine (max_width <=
        capacity), so the C prep is stubbed for the failing window."""
        eng = _engine()
        real = native.prep_pack_columnar
        calls = {"n": 0}

        def failing(directory, n, *args):
            calls["n"] += 1
            if calls["n"] == 2:
                return (native.PREP_OVERCOMMIT, None, None,
                        np.empty((0, 8), np.int64))
            return real(directory, n, *args)

        wins_reqs = [[RateLimitReq(name="o", unique_key=f"w{w}k{i}",
                                   hits=1, limit=50, duration=60_000)
                      for i in range(10)] for w in range(3)]
        try:
            native.prep_pack_columnar = failing
            h = eng.launch_columnar_windows(
                [cols_from(rs) for rs in wins_reqs], SLOW, now_ms=NOW)
        finally:
            native.prep_pack_columnar = real
        assert h is not None
        assert len(h[0]) == 1  # only the pre-failure window consumed
        assert "over-committed" in h[1]
        outs = [_outs(10)]
        lefts = eng.collect_columnar_windows(h, outs)
        assert len(lefts[0]) == 0
        assert outs[0][2].tolist() == [49] * 10  # prefix really decided

    def test_mixed_width_group_after_bucket_splits(self):
        """A chunk one item over a window boundary: the tail sub-window
        rides the same scan group at the group's max bucket width."""
        eng = _engine()
        reqs = [RateLimitReq(name="mx", unique_key=f"t{i}", hits=1,
                             limit=10, duration=60_000) for i in range(33)]
        (st, _li, re, _rs), stats = run_pipelined(eng, reqs, NOW,
                                                  depth=2, scan=4)
        assert (st == 0).all() and (re == 9).all()
        assert stats["groups"] == 1  # [16, 16, 1] in one launch


class TestBucketSplits:
    def test_pow2_max_width_matches_raw_stepping(self):
        assert bucket_splits(300, 8, 256) == [256, 44]
        assert bucket_splits(256, 8, 256) == [256]
        assert bucket_splits(257, 8, 256) == [256, 1]
        assert bucket_splits(7, 8, 256) == [7]

    def test_capped_non_pow2_max_width_stays_on_ladder(self):
        """A capacity-capped engine (max_width not a power of two) splits
        on the pow2 ladder instead of minting the capped terminal shape
        per piece."""
        splits = bucket_splits(10_001, 64, 5000)
        assert splits == [4096, 4096, 1809]
        for ln in splits[:-1]:
            assert bucket_width(ln, 64, 5000) == ln  # zero padding
        assert sum(splits) == 10_001

    def test_splits_cover_and_fit(self):
        rng = np.random.default_rng(5)
        for _ in range(50):
            n = int(rng.integers(1, 40_000))
            lo = int(2 ** rng.integers(3, 7))
            hi = int(rng.integers(lo, 10_000))
            splits = bucket_splits(n, lo, hi)
            assert sum(splits) == n
            assert all(0 < ln <= hi for ln in splits)


class TestShardedColumnarPipeline:
    def test_mesh_pipelined_bit_exact(self):
        """The mesh twin: pipelined columnar launches agree with the
        lock-step mesh columnar path and the single-table object path."""
        from gubernator_tpu.parallel import ShardedEngine

        host = Engine(capacity=2048, min_width=8, max_width=16)
        lock = ShardedEngine(n_shards=4, capacity_per_shard=512,
                             min_width=8, max_width=16)
        pipe = ShardedEngine(n_shards=4, capacity_per_shard=512,
                             min_width=8, max_width=16)
        if not pipe.supports_columnar():
            pytest.skip("native routing prep unavailable")
        rng = np.random.default_rng(29)
        for it in range(8):
            n = int(rng.integers(10, 90))
            reqs = [RateLimitReq(
                name="sm", unique_key=f"k{rng.integers(0, 30)}",
                hits=int(rng.integers(0, 3)), limit=25, duration=60_000)
                for _ in range(n)]
            now = NOW + it * 700
            want = host.get_rate_limits(reqs, now_ms=now)
            lk = run_lockstep(lock, reqs, now)
            (st, li, re, rs), _ = run_pipelined(pipe, reqs, now,
                                                depth=3, scan=2)
            for i, w in enumerate(want):
                w_t = (w.status, w.limit, w.remaining, w.reset_time)
                assert (lk[0][i], lk[1][i], lk[2][i], lk[3][i]) == w_t, \
                    (it, i, "mesh lockstep")
                assert (st[i], li[i], re[i], rs[i]) == w_t, \
                    (it, i, "mesh pipelined")


def pin_engine_clock(monkeypatch, now_ms=NOW):
    """Pin the clock every Engine entry reads when no now_ms is passed
    (the served path passes none), so a served answer can be held to an
    engine-level reference at the same instant — reset_time included.
    Returns the holder; set holder["now"] to move the clock."""
    import gubernator_tpu.models.engine as engine_mod

    holder = {"now": now_ms}
    monkeypatch.setattr(engine_mod, "millisecond_now",
                        lambda: holder["now"])
    return holder


def _rows(resps):
    return [(int(r.status), r.limit, r.remaining, r.reset_time)
            for r in resps]


def reference_rows(eng, reqs, now_ms):
    """run_lockstep's answers as (status, limit, remaining, reset_time)
    rows: submit_/complete_columnar on a twin engine, leftovers through
    the object path."""
    st, li, re, rs = run_lockstep(eng, reqs, now_ms)
    return list(zip(st.tolist(), li.tolist(), re.tolist(), rs.tolist()))


def assert_served_rows(got, want, reqs, tag):
    """Served RateLimitResps against reference_rows, column for column.
    An invalid item is a zero lane in the reference and a zero lane plus
    a message on the wire."""
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        if g.error:
            assert w == (0, 0, 0, 0), (tag, i, reqs[i], g, w)
        assert _rows([g])[0] == w, (tag, i, reqs[i], g, w)


def _serve(eng, **kw):
    from gubernator_tpu.service.config import InstanceConfig
    from gubernator_tpu.service.instance import Instance
    from gubernator_tpu.service.peerlink import (
        PeerLinkClient,
        PeerLinkService,
    )

    inst = Instance(InstanceConfig(backend=eng), advertise_address="self")
    svc = PeerLinkService(inst, port=0, **kw)
    cli = PeerLinkClient(f"127.0.0.1:{svc.port}")
    return inst, svc, cli


class TestWireLevelDifferential:
    def test_wire_hammer_pipelined_vs_lockstep(self, monkeypatch):
        """Wide peer-hop frames (duplicates, gregorian, GLOBAL, invalid
        keys) through a PIPELINED service must come back bit-identical to
        the lock-step engine-level reference (run_lockstep on a twin
        engine) — every column, reset_time included: the clock is
        pinned."""
        from gubernator_tpu.service.peerlink import (
            METHOD_GET_PEER_RATE_LIMITS,
        )

        clock = pin_engine_clock(monkeypatch)
        ip, sp, cp = _serve(_engine(), pipeline_depth=3, pipeline_scan=4)
        twin = _engine()
        rng = np.random.default_rng(41)
        try:
            for it in range(6):
                clock["now"] = NOW + it * 500
                reqs = _random_reqs(rng, int(rng.integers(40, 150)),
                                    n_keys=20)
                # a GLOBAL lane demotes to the leftover path on both
                reqs[int(rng.integers(0, len(reqs)))] = RateLimitReq(
                    name="cp", unique_key=f"gl{it}", hits=1, limit=9,
                    duration=60_000, behavior=int(Behavior.GLOBAL))
                got = cp.call(METHOD_GET_PEER_RATE_LIMITS, reqs, 30.0)
                assert_served_rows(
                    got, reference_rows(twin, reqs, clock["now"]), reqs, it)
            assert sp.stats["columnar_windows"] > 0
            assert sp.stats["columnar_groups"] > 0
        finally:
            cp.close()
            sp.close()
            ip.close()

    def test_wire_over_commit_error_fill(self):
        """Over-commit mid-chunk on the wire: the unconsumed remainder
        gets per-item error replies, the prefix still decides, and the
        pull is answered (no stranded frames)."""
        from gubernator_tpu.service.peerlink import (
            METHOD_GET_PEER_RATE_LIMITS,
        )

        eng = _engine()
        ip, sp, cp = _serve(eng, pipeline_depth=3, pipeline_scan=2)
        real = native.prep_pack_columnar
        calls = {"n": 0}

        def failing(directory, n, *args):
            calls["n"] += 1
            if calls["n"] == 2:
                return (native.PREP_OVERCOMMIT, None, None,
                        np.empty((0, 8), np.int64))
            return real(directory, n, *args)

        reqs = [RateLimitReq(name="oc", unique_key=f"k{i}", hits=1,
                             limit=50, duration=60_000) for i in range(48)]
        try:
            native.prep_pack_columnar = failing
            out = cp.call(METHOD_GET_PEER_RATE_LIMITS, reqs, 30.0)
        finally:
            native.prep_pack_columnar = real
            cp.close()
            sp.close()
            ip.close()
        assert len(out) == 48
        # first sub-window (16 items at max_width 16) decided
        assert all(r.error == "" and r.remaining == 49 for r in out[:16])
        # the failing window and everything after error-fills
        assert all("over-committed" in r.error for r in out[16:])

    def test_clean_drain_on_service_close(self):
        """Frames in flight when the service closes either complete or
        fail loudly (PeerLinkError) — never hang; the engine stays
        consistent afterwards."""
        from gubernator_tpu.service.peerlink import (
            METHOD_GET_PEER_RATE_LIMITS,
            PeerLinkError,
        )

        eng = _engine()
        ip, sp, cp = _serve(eng, pipeline_depth=3, pipeline_scan=4)
        errs = []
        done = []

        def caller(i):
            reqs = [RateLimitReq(name="dr", unique_key=f"c{i}_{j}", hits=1,
                                 limit=10, duration=60_000)
                    for j in range(64)]
            try:
                done.append(cp.call(METHOD_GET_PEER_RATE_LIMITS, reqs,
                                    10.0))
            except PeerLinkError:
                done.append(None)
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=caller, args=(i,), daemon=True)
              for i in range(6)]
        for t in ts:
            t.start()
        sp.close()  # races the calls deliberately
        for t in ts:
            t.join(timeout=20)
        assert not any(t.is_alive() for t in ts)
        assert not errs
        cp.close()
        ip.close()
        # the engine survived the drain: fresh decisions are exact
        out = eng.get_rate_limits(
            [RateLimitReq(name="dr", unique_key="post", hits=1, limit=5,
                          duration=60_000)], now_ms=NOW)
        assert out[0].remaining == 4


class TestSaturationDemotion:
    @pytest.mark.parametrize("n", [1, 40])
    def test_saturated_chunk_is_shed_by_the_object_path(self, n):
        """Admission saturated: a columnar chunk must not reach the
        device. The lone request (the seed-before-post branch of
        _handle_batch) and the wide chunk (_columnar_chunk) are both
        demoted to the object path, whose admission gate answers
        RESOURCE_EXHAUSTED rows; nothing is deducted, and when the
        pressure clears the same frame decides from a full bucket."""
        from gubernator_tpu.service.peerlink import (
            METHOD_GET_PEER_RATE_LIMITS,
        )

        eng = _engine()
        ip, sp, cp = _serve(eng, pipeline_depth=3, pipeline_scan=4)
        if n == 1 and sp._seed_engine is None:
            cp.close()
            sp.close()
            ip.close()
            pytest.skip("no native lone-request mirror on this build")
        reqs = [RateLimitReq(name="sat", unique_key=f"s{i}", hits=1,
                             limit=10, duration=60_000) for i in range(n)]
        try:
            ip.conf.behaviors.max_pending = 8
            ip._forward_inflight = 16  # 2x saturation
            windows = eng.stats.batches
            out = cp.call(METHOD_GET_PEER_RATE_LIMITS, reqs, 30.0)
            assert len(out) == n
            assert all("RESOURCE_EXHAUSTED" in r.error for r in out), out
            assert eng.stats.batches == windows  # no window launched
            assert sp.stats["columnar_windows"] == 0
            ip._forward_inflight = 0
            out = cp.call(METHOD_GET_PEER_RATE_LIMITS, reqs, 30.0)
            assert [(r.error, r.remaining) for r in out] == [("", 9)] * n
        finally:
            cp.close()
            sp.close()
            ip.close()


class TestAutotuneDepthOne:
    def test_probe_set_includes_lockstep(self):
        """The default probe set starts at depth 1 so a host where
        overlap loses auto-degrades instead of staying pinned."""
        import inspect

        from gubernator_tpu.service.combiner import BackendCombiner

        sig = inspect.signature(BackendCombiner.autotune)
        assert sig.parameters["depths"].default[0] == 1

    def test_depth_one_winner_degrades_to_serial(self):
        from gubernator_tpu.service.combiner import BackendCombiner

        eng = _engine()
        if not eng.supports_pipeline():
            pytest.skip("native prep unavailable")
        c = BackendCombiner(eng, depth="auto")
        try:
            assert c.pipelined
            d = c.autotune(depths=(1,), probe_windows=3)
            assert d == 1
            assert not c.pipelined  # serial lock-step from here on
            assert c.depth == 1
            out = c.submit([RateLimitReq(name="at", unique_key="k",
                                         hits=1, limit=9,
                                         duration=60_000)], NOW)
            assert out[0].remaining == 8
            assert c.stats["pipelined_windows"] == 0
        finally:
            c.close()


class TestRunSidecar:
    """_run_sidecar turns a span's (item index, bytes) pairs into the
    offset column + blob pls_send_partial takes, and removes them from
    the pull's list (each row posts once)."""

    def test_in_order_pairs(self):
        from gubernator_tpu.service.peerlink import PeerLinkService

        pairs = [(0, b"aa"), (2, b"b"), (4, b"ccc"), (7, b"later")]
        off, blob = PeerLinkService._run_sidecar(pairs, 0, 5)
        assert blob == b"aabccc"
        assert off.tolist() == [0, 2, 2, 3, 3, 6]
        assert pairs == [(7, b"later")]  # the next span's entry stays

    def test_out_of_order_pairs_still_correct(self):
        """Inline object retirement interleaves with group drains, so
        entries may sit out of index order; offsets are span-relative."""
        from gubernator_tpu.service.peerlink import PeerLinkService

        pairs = [(14, b"ccc"), (1, b"early"), (10, b"aa"), (12, b"b")]
        off, blob = PeerLinkService._run_sidecar(pairs, 10, 15)
        assert blob == b"aabccc"
        assert off.tolist() == [0, 2, 2, 3, 3, 6]
        assert pairs == [(1, b"early")]

    def test_empty_pairs_zero_offsets(self):
        from gubernator_tpu.service.peerlink import PeerLinkService

        for pairs in ([], [(9, b"elsewhere")]):
            kept = list(pairs)
            off, blob = PeerLinkService._run_sidecar(pairs, 0, 5)
            assert blob == b""
            assert off.tolist() == [0, 0, 0, 0, 0, 0]
            assert pairs == kept

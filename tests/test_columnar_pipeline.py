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
import time

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

    def test_a_group_launches_max_width_wide_and_a_cut_first_window_alone(
            self):
        """A group of K > 1 windows launches `max_width` wide whatever
        its windows' own bucket widths are (the group shapes are compiled
        at the top width only); a group whose FIRST window has leftovers
        is that window alone and launches at its own bucket width, as
        submit_columnar launches it."""
        eng = _engine()  # ladder 8, 16
        stacks, singles = [], []
        scan, single = eng._dispatch_scan_staged, eng._dispatch_staged
        eng._dispatch_scan_staged = lambda stacked, *a, **k: (
            stacks.append(stacked.shape), scan(stacked, *a, **k))[1]
        eng._dispatch_staged = lambda packed, *a, **k: (
            singles.append(packed.shape), single(packed, *a, **k))[1]
        wins = [[RateLimitReq(name="gw", unique_key=f"w{w}k{i}", hits=1,
                              limit=9, duration=60_000) for i in range(5)]
                for w in range(3)]
        h = eng.launch_columnar_windows([cols_from(rs) for rs in wins],
                                        SLOW, now_ms=NOW)
        outs = [_outs(5) for _ in range(3)]
        assert [len(x) for x in eng.collect_columnar_windows(h, outs)] == \
            [0, 0, 0]
        assert stacks == [(4, 9, 16)] and singles == []
        assert all(o[2].tolist() == [8] * 5 for o in outs)
        wins[0][4] = wins[0][0]  # the first window repeats a key: it cuts
        h = eng.launch_columnar_windows([cols_from(rs) for rs in wins],
                                        SLOW, now_ms=NOW)
        assert len(h[0]) == 1
        outs = [_outs(5)]
        left, = eng.collect_columnar_windows(h, outs)
        assert left.tolist() == [4]
        assert stacks == [(4, 9, 16)] and singles == [(9, 8)]
        assert outs[0][2][:4].tolist() == [7] * 4

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

    def test_reused_staging_holds_nothing_of_the_launch_before(self):
        """A staging buffer of the ring is reused by the next launch of
        its shape: after a full window, a short one on the same buffer
        must ship padding lanes that carry no slot and no column of the
        launch before."""
        eng = _engine()
        staging = {}
        wide = [RateLimitReq(name="sg", unique_key=f"w{i}", hits=2,
                             limit=77, duration=90_000,
                             algorithm=Algorithm.LEAKY_BUCKET)
                for i in range(16)]
        short = [RateLimitReq(name="sg", unique_key=f"s{i}", hits=1,
                              limit=10, duration=60_000) for i in range(9)]
        for reqs, want in ((wide, 75), (short, 9), (wide, 73)):
            h = eng.launch_columnar_windows([cols_from(reqs)], SLOW,
                                            now_ms=NOW, staging=staging)
            outs = [_outs(len(reqs))]
            eng.collect_columnar_windows(h, outs)
            assert outs[0][2].tolist() == [want] * len(reqs)
            buf, = staging.values()
            n = len(reqs)
            assert (buf[0, 0, n:] == -1).all()
            assert not buf[0, 1:, n:].any()

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


def chunk_cap(monkeypatch, n):
    """Cut pulls into chunks of <= n items, as MAX_BATCH_SIZE (1000) cuts a
    pull of 1000-request calls: with frames of n items every chunk is one
    frame, and at an engine whose widest window holds n it is ONE window —
    the shape of every chunk at the shipped widths."""
    import gubernator_tpu.service.peerlink as peerlink_mod

    monkeypatch.setattr(peerlink_mod, "MAX_BATCH_SIZE", n)


def send_as_one_pull(svc, cli, frames, methods=None, pulls=None):
    """Send `frames` on one connection so that they reach the (single)
    worker in at most two pulls, the last of them holding several frames:
    the worker is held inside its first pull until the IO thread has
    parsed every frame, so the rest queue up and are pulled together.
    `methods` gives each frame's method (peer hops by default); `pulls`
    collects (ctx, got, the pull's method column) as the worker handles
    them. Returns each frame's answers, in frame order."""
    from gubernator_tpu.service.peerlink import METHOD_GET_PEER_RATE_LIMITS

    gate = threading.Event()
    real = svc._handle_batch

    def gated(got, b, ctx, ws):
        gate.wait(10.0)
        if pulls is not None:
            pulls.append((ctx, got, b["method"][:got].tolist()))
        return real(got, b, ctx=ctx, ws=ws)

    svc._handle_batch = gated
    try:
        futs = [cli.call_async(m, f)[0] for m, f in zip(
            methods or [METHOD_GET_PEER_RATE_LIMITS] * len(frames), frames)]
        deadline = time.time() + 10
        while (svc.wire_pending_count() < len(frames)
               and time.time() < deadline):
            time.sleep(0.002)
        assert svc.wire_pending_count() == len(frames)
        gate.set()
        return [f.result(30.0) for f in futs]
    finally:
        gate.set()
        svc._handle_batch = real


def send_in_one_pull(svc, cli, frames, pulls=None):
    """Send `frames` so that they reach the (single) worker in ONE pull:
    a two-request primer frame (keys of its own) holds the worker inside
    its pull until the IO thread has parsed every frame, so all of them
    queue up and are pulled together. `pulls` collects (got, launches
    still in flight when the pull's handling returned) of the pulls after
    the primer's. Returns each frame's answers, in frame order."""
    from gubernator_tpu.service.peerlink import METHOD_GET_PEER_RATE_LIMITS

    entered, gate = threading.Event(), threading.Event()
    real = svc._handle_batch
    handled = [0]  # items of the pulls after the primer's, once recorded

    def gated(got, b, ctx, ws):
        primer = not entered.is_set()
        entered.set()
        if primer:
            gate.wait(10.0)
        try:
            return real(got, b, ctx=ctx, ws=ws)
        finally:
            if not primer:
                if pulls is not None:
                    pulls.append((got, len(ws["inflight"])))
                handled[0] += got

    svc._handle_batch = gated
    try:
        tag = time.monotonic_ns()
        head = cli.call_async(METHOD_GET_PEER_RATE_LIMITS, [RateLimitReq(
            name="primer", unique_key=f"{tag}_{i}", hits=0, limit=1,
            duration=60_000) for i in range(2)])[0]
        assert entered.wait(10.0)
        futs = [cli.call_async(METHOD_GET_PEER_RATE_LIMITS, f)[0]
                for f in frames]
        deadline = time.time() + 10
        while (svc.wire_pending_count() < len(frames) + 1
               and time.time() < deadline):
            time.sleep(0.002)
        assert svc.wire_pending_count() == len(frames) + 1
        gate.set()
        head.result(30.0)
        out = [f.result(30.0) for f in futs]
        # the answers leave inside the pull's handling: wait for its end
        deadline = time.time() + 10
        while (handled[0] < sum(len(f) for f in frames)
               and time.time() < deadline):
            time.sleep(0.001)
        return out
    finally:
        gate.set()
        svc._handle_batch = real


def lockstep_backend(eng):
    """`eng` as a backend whose group is NOT one launch (the mesh engine
    says so of itself): the pull loop keeps one-window chunks lock-step."""
    eng.columnar_group_is_one_launch = False
    return eng


def spy_launches(eng, svc):
    """Record what the pull loop asks of the engine: the window count of
    every launch_columnar_windows call, and the spans of every
    _columnar_chunk_lockstep call."""
    groups, lockstep = [], []
    real_launch, real_lock = (eng.launch_columnar_windows,
                              svc._columnar_chunk_lockstep)
    eng.launch_columnar_windows = lambda wins, *a, **k: (
        groups.append(len(wins)), real_launch(wins, *a, **k))[1]
    svc._columnar_chunk_lockstep = lambda *a, **k: (
        lockstep.append(a[2]), real_lock(*a, **k))[1]
    return groups, lockstep


def oracle_rows(table, reqs, now_ms):
    """ops/oracle.py's answers for `reqs`, one after another against
    `table`, as (status, limit, remaining, reset_time) rows."""
    from gubernator_tpu.ops.oracle import oracle_answer

    return _rows([oracle_answer(table, r, now_ms) for r in reqs])


def _shared_key_frames(rng, n_frames, n, leftovers):
    """`n_frames` frames of `n` requests: keys distinct inside a frame,
    a hot set shared by all of them (the hot keys of several calls), both
    algorithms. With `leftovers` (True: every other frame; or the frames'
    indices), a frame also repeats one of its own keys, carries a
    gregorian and a GLOBAL request: lanes the C prep demotes to the
    object path."""
    if leftovers is True:
        leftovers = range(0, n_frames, 2)
    leftovers = set(leftovers or ())
    frames = []
    for f in range(n_frames):
        keys = [f"hot{i}" for i in rng.permutation(6)[:4]]
        keys += [f"f{f}_{i}" for i in range(n - len(keys))]
        reqs = [RateLimitReq(
            name="mf", unique_key=k, hits=int(rng.integers(0, 3)), limit=30,
            duration=60_000,
            algorithm=(Algorithm.LEAKY_BUCKET if k.startswith("hot")
                       and int(k[3:]) % 2 else Algorithm.TOKEN_BUCKET))
            for k in keys]
        if f in leftovers:
            reqs[-1] = RateLimitReq(name="mf", unique_key=keys[0], hits=1,
                                    limit=30, duration=60_000,
                                    algorithm=reqs[0].algorithm)
            reqs[-2] = RateLimitReq(
                name="mf", unique_key=f"greg{f}", hits=1, limit=30,
                duration=1, behavior=int(Behavior.DURATION_IS_GREGORIAN))
            reqs[-3] = RateLimitReq(
                name="mf", unique_key=f"glob{f}", hits=1, limit=30,
                duration=60_000, behavior=int(Behavior.GLOBAL))
        frames.append(reqs)
    return frames


class TestWireLevelDifferential:
    @pytest.mark.parametrize("max_width", [16, 256])
    def test_wire_hammer_pipelined_vs_lockstep(self, monkeypatch,
                                               max_width):
        """Peer-hop frames (duplicates, gregorian, GLOBAL, invalid keys)
        through a PIPELINED service must come back bit-identical to the
        lock-step engine-level reference (run_lockstep on a twin engine)
        — every column, reset_time included: the clock is pinned. At
        width 16 a frame is many windows, launched in scan groups; at 256
        it is ONE window and is served lock-step (submit_/
        complete_columnar), its leftovers retired before the next."""
        from gubernator_tpu.service.peerlink import (
            METHOD_GET_PEER_RATE_LIMITS,
        )

        clock = pin_engine_clock(monkeypatch)
        ip, sp, cp = _serve(_engine(max_width), pipeline_depth=3,
                            pipeline_scan=4)
        twin = _engine(max_width)
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
            if max_width == 256:  # one window a frame: nothing launched
                assert sp.stats["columnar_windows"] == 0
            else:
                assert sp.stats["columnar_windows"] > 0
                assert sp.stats["columnar_groups"] > 0
        finally:
            cp.close()
            sp.close()
            ip.close()

    @pytest.mark.parametrize("grouped", [True, False],
                             ids=["group", "lockstep_backend"])
    @pytest.mark.parametrize("leftovers", [False, True],
                             ids=["clean", "leftovers"])
    def test_multi_frame_pull_of_one_window_chunks(self, monkeypatch,
                                                   leftovers, grouped):
        """The shape of a batch1000 pull: several frames pulled together,
        each a chunk that fits ONE window, keys shared between frames.
        The run of chunks is handed to the engine as ONE scan group a
        pull (launch_/collect_columnar_windows, collected inside the
        pull: nothing is in flight at its end), and every column of every
        answer is that of run_lockstep on a twin engine and of
        ops/oracle.py, in frame order — leaky buckets and reset_time
        included. With leftovers (a key repeated inside its frame,
        gregorian, GLOBAL) a frame's tail retires through the object path
        before the next frame, which asks for the same hot keys, is
        prepped: the group is cut there. On a backend whose group is not
        one launch (`lockstep_backend`: the mesh's statement) every chunk
        is served lock-step inside the pull (submit_/complete_columnar)
        and nothing is launched."""
        clock = pin_engine_clock(monkeypatch)
        chunk_cap(monkeypatch, 16)
        eng = _engine() if grouped else lockstep_backend(_engine())
        ip, sp, cp = _serve(eng, pipeline_depth=3, pipeline_scan=4,
                            workers=1)
        groups, lockstep = spy_launches(eng, sp)
        twin = _engine()
        table = {}
        rng = np.random.default_rng(53)
        pulls = []
        try:
            for it in range(4):
                clock["now"] = NOW + it * 900
                frames = _shared_key_frames(rng, 4, 16, leftovers)
                got = send_in_one_pull(sp, cp, frames, pulls)
                for f, (reqs, out) in enumerate(zip(frames, got)):
                    want = reference_rows(twin, reqs, clock["now"])
                    assert_served_rows(out, want, reqs, (it, f))
                    assert oracle_rows(table, reqs, clock["now"]) == want
            assert pulls == [(64, 0)] * 4  # one pull, nothing in flight
            assert all(len(spans) == 1 for spans in lockstep)
            if not grouped:
                assert groups == []
                assert len(lockstep) == 4 + 16  # primers, then every chunk
                assert sp.stats["columnar_windows"] == 0
            elif not leftovers:
                assert groups == [4] * 4  # one group a run
                assert len(lockstep) == 4  # the primers
                assert sp.stats["columnar_windows"] == 16
                assert sp.stats["columnar_groups"] == 4
                assert sp.stats["columnar_cuts"] == 0
            else:
                # frame 0 has leftovers: the first window cuts its group
                # and the rest of the run goes on chunk by chunk
                assert groups == [4] * 4
                assert len(lockstep) == 4 + 12
                assert sp.stats["columnar_windows"] == 4
                assert sp.stats["columnar_cuts"] == 4
            assert sp.stats["pull_boundary_stalls"] == 0
            assert sp.stats["columnar_fill_stalls"] == 0
            assert sp.stats["errors"] == 0
        finally:
            del eng.launch_columnar_windows
            cp.close()
            sp.close()
            ip.close()

    @pytest.mark.parametrize("n_frames, groups_want", [
        (2, [2]), (3, [3]), (4, [4]), (5, [4]), (6, [4, 2]), (7, [7]),
        (9, [8])])
    def test_a_pull_of_n_frames_is_served_in_groups_by_n_alone(
            self, monkeypatch, n_frames, groups_want):
        """A pull of n one-window frames, keys shared between frames,
        clean: the run is cut into groups by n alone (a power of two, or
        one window short of one; a chunk left over is served alone), each
        group one launch collected inside the pull, and every column of
        every answer is run_lockstep's on a twin engine and
        ops/oracle.py's, in frame order, on a pinned clock."""
        clock = pin_engine_clock(monkeypatch)
        chunk_cap(monkeypatch, 16)
        eng = _engine()
        ip, sp, cp = _serve(eng, pipeline_depth=3, pipeline_scan=8,
                            workers=1)
        groups, lockstep = spy_launches(eng, sp)
        twin, table = _engine(), {}
        rng = np.random.default_rng(59 + n_frames)
        pulls = []
        try:
            for it in range(3):
                clock["now"] = NOW + it * 900
                frames = _shared_key_frames(rng, n_frames, 16, False)
                got = send_in_one_pull(sp, cp, frames, pulls)
                for f, (reqs, out) in enumerate(zip(frames, got)):
                    want = reference_rows(twin, reqs, clock["now"])
                    assert_served_rows(out, want, reqs, (it, f))
                    assert oracle_rows(table, reqs, clock["now"]) == want
            assert pulls == [(16 * n_frames, 0)] * 3
            assert groups == groups_want * 3
            alone = n_frames - sum(groups_want)
            assert len(lockstep) == 3 * (1 + alone)  # the primer, the rest
            assert sp.stats["columnar_windows"] == 3 * sum(groups_want)
            assert sp.stats["columnar_groups"] == 3 * len(groups_want)
            assert sp.stats["columnar_cuts"] == 0
            assert sp.stats["pull_boundary_stalls"] == 0
            assert sp.stats["errors"] == 0
        finally:
            del eng.launch_columnar_windows
            cp.close()
            sp.close()
            ip.close()

    @pytest.mark.parametrize("where, groups_want, windows, alone", [
        # the first window cuts: the rest of the run chunk by chunk
        ("first", [4], 1, 4),
        # a middle one: the group is cut behind it, the rest grouped anew
        ("middle", [4, 2], 5, 0),
        # the last of the run: served alone, nothing behind it to cut
        ("last", [4], 4, 1)])
    def test_a_frame_with_leftovers_cuts_its_group(self, monkeypatch, where,
                                                   groups_want, windows,
                                                   alone):
        """Five frames in one pull, one of them with lanes the C prep
        demotes (a key repeated inside the frame, gregorian, GLOBAL): its
        window is its group's last, its tail retires through the object
        path before the next frame — which asks for the same hot keys —
        is prepped, and the answers are lock-step's and the oracle's."""
        clock = pin_engine_clock(monkeypatch)
        chunk_cap(monkeypatch, 16)
        eng = _engine()
        ip, sp, cp = _serve(eng, pipeline_depth=3, pipeline_scan=8,
                            workers=1)
        groups, lockstep = spy_launches(eng, sp)
        twin, table = _engine(), {}
        rng = np.random.default_rng(67)
        at = {"first": 0, "middle": 2, "last": 4}[where]
        pulls = []
        try:
            frames = _shared_key_frames(rng, 5, 16, [at])
            got = send_in_one_pull(sp, cp, frames, pulls)
            for f, (reqs, out) in enumerate(zip(frames, got)):
                want = reference_rows(twin, reqs, clock["now"])
                assert_served_rows(out, want, reqs, (where, f))
                assert oracle_rows(table, reqs, clock["now"]) == want
            assert pulls == [(80, 0)]
            assert groups == groups_want
            assert len(lockstep) == 1 + alone
            assert sp.stats["columnar_windows"] == windows
            assert sp.stats["columnar_cuts"] == (0 if where == "last" else 1)
            assert sp.stats["leftover_items"] == 3
            assert sp.stats["errors"] == 0
        finally:
            del eng.launch_columnar_windows
            cp.close()
            sp.close()
            ip.close()

    def test_over_commit_in_the_middle_of_a_group(self, monkeypatch):
        """The second of four frames over-commits the directory: the
        window before it was prepped and is dispatched and decided, the
        failing frame error-fills whole (as a lock-step chunk does), and
        the two frames behind it are served as a group of their own."""
        chunk_cap(monkeypatch, 16)
        eng = _engine()
        ip, sp, cp = _serve(eng, pipeline_depth=3, pipeline_scan=8,
                            workers=1)
        groups, lockstep = spy_launches(eng, sp)
        real = native.prep_pack_columnar
        calls = {"n": 0}

        def failing(directory, n, *args):
            # the primer's lone chunk is prep 1; the group's windows 2..
            calls["n"] += 1
            if calls["n"] == 3:
                return (native.PREP_OVERCOMMIT, None, None,
                        np.empty((0, 8), np.int64))
            return real(directory, n, *args)

        frames = [[RateLimitReq(name="oc", unique_key=f"f{f}k{i}", hits=1,
                                limit=50, duration=60_000)
                   for i in range(16)] for f in range(4)]
        pulls = []
        try:
            native.prep_pack_columnar = failing
            got = send_in_one_pull(sp, cp, frames, pulls)
        finally:
            native.prep_pack_columnar = real
            del eng.launch_columnar_windows
            cp.close()
            sp.close()
            ip.close()
        assert pulls == [(64, 0)]
        assert groups == [4, 2] and len(lockstep) == 1
        for f in (0, 2, 3):
            assert [(r.error, r.remaining) for r in got[f]] == \
                [("", 49)] * 16, f
        assert all("over-committed" in r.error for r in got[1])
        assert sp.stats["columnar_windows"] == 3
        assert sp.stats["columnar_cuts"] == 0

    def test_a_group_collect_that_raises_answers_the_whole_pull(
            self, monkeypatch):
        """The readback of a pull's group raises: _recover_batch answers
        every row of the pull with the internal-failure reply (nothing
        of it was posted), no frame is stranded, and the worker serves
        the next pull."""
        chunk_cap(monkeypatch, 16)
        eng = _engine()
        ip, sp, cp = _serve(eng, pipeline_depth=3, pipeline_scan=8,
                            workers=1)
        real_collect = eng.collect_columnar_windows
        calls = {"n": 0}

        def first_raises(handle, outs):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected readback failure")
            return real_collect(handle, outs)

        frames = [[RateLimitReq(name="gc", unique_key=f"f{f}k{i}", hits=1,
                                limit=20, duration=60_000)
                   for i in range(16)] for f in range(3)]
        try:
            eng.collect_columnar_windows = first_raises
            got = send_in_one_pull(sp, cp, frames)
            for out in got:
                assert {(r.error, r.remaining) for r in out} == \
                    {("peerlink: internal batch failure", 0)}
            assert sp.stats["errors"] == 1
            assert sp.wire_pending_count() == 0
            # the group's hits were applied at its launch; the pull after
            # it is served, a second hit a key
            again = send_in_one_pull(sp, cp, frames)
            for out in again:
                assert [(r.error, r.remaining) for r in out] == \
                    [("", 18)] * 16
        finally:
            del eng.collect_columnar_windows
            cp.close()
            sp.close()
            ip.close()

    @pytest.mark.parametrize("cap", [None, 16], ids=["one_chunk", "chunks"])
    def test_wire_over_commit_error_fill(self, monkeypatch, cap):
        """Over-commit on the wire: the unconsumed remainder of the chunk
        gets per-item error replies, what was prepped before still
        decides, and the pull is answered (no stranded frames). As one
        chunk of three windows the failing window and everything after
        error-fills; as three one-window chunks the failing chunk
        error-fills whole and the chunk after it decides."""
        from gubernator_tpu.service.peerlink import (
            METHOD_GET_PEER_RATE_LIMITS,
        )

        if cap is not None:
            chunk_cap(monkeypatch, cap)
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
        # the failing window error-fills; so does everything after it in
        # the same chunk
        assert all("over-committed" in r.error for r in out[16:32])
        if cap is None:
            assert all("over-committed" in r.error for r in out[32:])
        else:
            assert all(r.error == "" and r.remaining == 49
                       for r in out[32:])

    def test_recover_batch_with_earlier_launches_in_flight(self,
                                                           monkeypatch):
        """A launch that raises while launches of earlier chunks (and of
        an earlier pull) are still in flight: _recover_batch settles the
        pipeline first, so the rows already launched are answered with
        their decisions, and every row of the failed pull that was not is
        answered with an error — each row once, no frame stranded, and
        the engine keeps exactly the hits it answered. A chunk here is
        two windows, so it is launched."""
        chunk_cap(monkeypatch, 32)
        eng = _engine()
        ip, sp, cp = _serve(eng, pipeline_depth=3, pipeline_scan=4,
                            workers=1)
        real = eng.launch_columnar_windows
        calls = {"n": 0}

        def third_raises(*a, **k):
            calls["n"] += 1
            if calls["n"] == 3:
                raise RuntimeError("injected launch failure")
            return real(*a, **k)

        frames = [[RateLimitReq(name="rb", unique_key=f"f{f}k{i}", hits=1,
                                limit=20, duration=60_000)
                   for i in range(32)] for f in range(5)]
        try:
            eng.launch_columnar_windows = third_raises
            got = send_as_one_pull(sp, cp, frames)
            del eng.launch_columnar_windows
            decided = []
            for f, out in enumerate(got):
                assert len(out) == 32
                rows = {(r.error, r.remaining) for r in out}
                decided.append(rows == {("", 19)})
                if not decided[f]:
                    assert rows == {("peerlink: internal batch failure",
                                     0)}, (f, out)
            # launched before the failure: decided; the failing chunk:
            # errors; the frames behind it: errors where they shared its
            # pull, decisions where the worker pulled them afterwards
            assert decided[:3] == [True, True, False], decided
            assert sp.stats["errors"] == 1
            assert sp.wire_pending_count() == 0
            # the same frames again: decided rows spend a second hit,
            # error rows their first
            again = send_as_one_pull(sp, cp, frames)
            for f, out in enumerate(again):
                assert [(r.error, r.remaining) for r in out] == \
                    [("", 18 if decided[f] else 19)] * 32, f
        finally:
            cp.close()
            sp.close()
            ip.close()

    @pytest.mark.parametrize("where", ["boundary", "inside_next_pull"])
    def test_failed_collect_of_an_earlier_pull_is_answered(self,
                                                           monkeypatch,
                                                           where):
        """The readback of a launch raises after the pull that launched
        it has returned. `boundary`: the worker collects it between pulls
        (an empty poll), where no pull's try is open. `inside_next_pull`:
        the next pull is already queued, fills the pipe and drains the
        earlier pull's launches inside its own try. Either way the worker
        lives, the popped launch's rows are answered with the
        internal-failure reply (through its OWN pull's buffers), every
        other launch keeps its decisions, and the service serves on."""
        from gubernator_tpu.service.peerlink import (
            METHOD_GET_PEER_RATE_LIMITS,
        )

        chunk_cap(monkeypatch, 32)
        eng = _engine()
        ip, sp, cp = _serve(eng, pipeline_depth=3, pipeline_scan=4,
                            workers=1)
        real_collect = eng.collect_columnar_windows
        calls = {"n": 0}

        def second_raises(handle, outs):
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("injected readback failure")
            return real_collect(handle, outs)

        def frames_of(tag, n_frames):
            return [[RateLimitReq(name="fc", unique_key=f"{tag}f{f}k{i}",
                                  hits=1, limit=20, duration=60_000)
                     for i in range(32)] for f in range(n_frames)]

        def queued(n):
            deadline = time.time() + 10
            while sp.wire_pending_count() < n and time.time() < deadline:
                time.sleep(0.002)
            assert sp.wire_pending_count() == n

        first, second = frames_of("a", 2), frames_of("b", 3)
        futs = []
        real_handle = sp._handle_batch
        pulls = {"n": 0}

        def handle(got, b, ctx, ws):
            pulls["n"] += 1
            if pulls["n"] == 1:
                queued(len(first))  # both frames of the first pull
            real_handle(got, b, ctx=ctx, ws=ws)
            if pulls["n"] == 1 and where == "inside_next_pull":
                # the first pull's launches are in flight; queue the
                # second pull before the worker polls
                futs.extend(cp.call_async(METHOD_GET_PEER_RATE_LIMITS, f)[0]
                            for f in second)
                queued(len(first) + len(second))

        sp._handle_batch = handle
        try:
            eng.collect_columnar_windows = second_raises
            futs[:0] = [cp.call_async(METHOD_GET_PEER_RATE_LIMITS, f)[0]
                        for f in first]
            if where == "boundary":
                got = [f.result(30.0) for f in futs]
                futs.extend(cp.call_async(METHOD_GET_PEER_RATE_LIMITS, f)[0]
                            for f in second)
            got = [f.result(30.0) for f in futs]
            del eng.collect_columnar_windows
            rows = [{(r.error, r.remaining) for r in out} for out in got]
            failed = {("peerlink: internal batch failure", 0)}
            # the second collect is the first pull's second launch
            assert rows[0] == {("", 19)} and rows[1] == failed, rows
            if where == "boundary":
                assert rows[2:] == [{("", 19)}] * 3, rows
            else:
                # the second pull was interrupted: what it had launched
                # keeps its decisions, what it had not is error-filled
                assert rows[2] == {("", 19)}, rows
                assert rows[4] == failed, rows
            assert sp.stats["errors"] == 1
            assert sp.wire_pending_count() == 0
            # the worker is alive and the pipeline empty
            out = cp.call(METHOD_GET_PEER_RATE_LIMITS, first[0], 30.0)
            assert {(r.error, r.remaining) for r in out} == {("", 18)}
        finally:
            sp._handle_batch = real_handle
            cp.close()
            sp.close()
            ip.close()

    @pytest.mark.parametrize("max_width", [16, 64])
    def test_clean_drain_on_service_close(self, max_width):
        """Frames in flight when the service closes either complete or
        fail loudly (PeerLinkError) — never hang; the engine stays
        consistent afterwards. At width 64 every frame is one window,
        served lock-step inside its pull."""
        from gubernator_tpu.service.peerlink import (
            METHOD_GET_PEER_RATE_LIMITS,
            PeerLinkError,
        )

        eng = _engine(max_width)
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


class TestOneWindowCounters:
    """What the serving counters say: a pull's run of one-window chunks
    (every chunk at the shipped widths) is one group a run, collected
    inside the pull; a chunk wider than one window is launched into the
    worker pipeline and may stay in flight; a lone request, and every
    chunk on a backend whose group is not one launch, is served lock-step
    and counts none."""

    @pytest.mark.parametrize("windows, grouped", [
        (1, True), (1, False), (2, True)], ids=["1", "1-lockstep", "2"])
    def test_windows_count_launched_chunks_and_the_boundary_stalls(
            self, monkeypatch, windows, grouped):
        """Chunks of one window: the pull's four are ONE group, collected
        inside the pull, so the windows count, nothing is in flight when
        the worker goes back to the queue and there is no stall of either
        kind; on a backend whose group is not one launch they are served
        lock-step and count nothing. Chunks of two windows are one launch
        each into the worker pipeline: the windows count, four launches
        meet a pipe of three (fill stall), and the last of a pull is
        still in flight when the worker polls and finds nothing (boundary
        stall)."""
        n = 16 * windows
        chunk_cap(monkeypatch, n)
        eng = _engine() if grouped else lockstep_backend(_engine())
        ip, sp, cp = _serve(eng, pipeline_depth=3, pipeline_scan=4,
                            workers=1)
        pulls = []
        try:
            for it in range(3):
                frames = [[RateLimitReq(name="ct", unique_key=f"p{it}f{f}k{i}",
                                        hits=1, limit=9, duration=60_000)
                           for i in range(n)] for f in range(4)]
                for out in send_in_one_pull(sp, cp, frames, pulls):
                    assert [(r.error, r.remaining) for r in out] == \
                        [("", 8)] * n
            assert sp.stats["columnar_cuts"] == 0
            assert (sp.wire_debug()["pull_boundary_stalls"]
                    == sp.stats["pull_boundary_stalls"])
            if windows == 1:
                assert pulls == [(4 * n, 0)] * 3  # none in flight at its end
                assert sp.stats["columnar_windows"] == (12 if grouped else 0)
                assert sp.stats["columnar_groups"] == (3 if grouped else 0)
                assert sp.stats["pull_boundary_stalls"] == 0
                assert sp.stats["columnar_fill_stalls"] == 0
            else:
                assert sp.stats["columnar_windows"] == 24  # two a chunk
                assert sp.stats["columnar_groups"] == 12
                assert sp.stats["pull_boundary_stalls"] >= 3
                assert sp.stats["columnar_fill_stalls"] >= 3
        finally:
            cp.close()
            sp.close()
            ip.close()

    def test_lone_request_is_lockstep_and_seeds_before_its_post(self):
        """got == 1: the chunk is served submit_/complete_columnar inside
        _handle_batch, the key's mirror is seeded, and only then is the
        reply posted (a seed after the post could overwrite hits the IO
        thread applied natively in between). It counts no columnar
        window."""
        from gubernator_tpu.service.peerlink import (
            METHOD_GET_PEER_RATE_LIMITS,
        )

        eng = _engine()
        ip, sp, cp = _serve(eng, pipeline_depth=3, pipeline_scan=4,
                            workers=1)
        if sp._seed_engine is None:
            cp.close()
            sp.close()
            ip.close()
            pytest.skip("no native lone-request mirror on this build")
        events = []
        real_lock, real_seed, real_post = (
            sp._columnar_chunk_lockstep, eng.seed_mirror, sp._post_span)
        sp._columnar_chunk_lockstep = lambda *a, **k: (
            events.append("lockstep"), real_lock(*a, **k))[1]
        eng.seed_mirror = lambda key: (
            events.append("seed"), real_seed(key))[1]
        sp._post_span = lambda *a, **k: (
            events.append("post"), real_post(*a, **k))[1]
        try:
            out = cp.call(METHOD_GET_PEER_RATE_LIMITS,
                          [RateLimitReq(name="lone", unique_key="k", hits=1,
                                        limit=7, duration=60_000)], 30.0)
            assert (out[0].error, out[0].remaining) == ("", 6)
            assert events == ["lockstep", "seed", "post"]
            assert sp.stats["columnar_windows"] == 0
            assert sp.stats["pull_boundary_stalls"] == 0
        finally:
            del eng.seed_mirror
            cp.close()
            sp.close()
            ip.close()


class TestChunkCut:
    def test_chunks_end_at_a_method_change_or_at_the_cap(self, monkeypatch):
        """_handle_batch cuts a pull into chunks with one pass over the
        method column: every chunk is one method, at most the cap, and
        ends only where the method changes, the cap is reached or the
        pull ends — whatever frames the pull happened to hold, and
        whether a chunk is served alone or as a window of its run's group.
        The answers say every item was served once, in order."""
        from gubernator_tpu.service.peerlink import (
            METHOD_GET_PEER_RATE_LIMITS,
            METHOD_GET_RATE_LIMITS,
        )

        cap = 16
        chunk_cap(monkeypatch, cap)
        ip, sp, cp = _serve(_engine(), pipeline_depth=3, pipeline_scan=4,
                            workers=1)
        seen = []
        real_col, real_obj = sp._columnar_chunk, sp._object_chunk

        def spy_col(m, eng, j, k, ctx, ws):
            seen.append((ctx, m, j, k, ctx.b["method"][j:k].tolist()))
            return real_col(m, eng, j, k, ctx, ws)

        def spy_obj(m, j, k, b, errs, metas, direct=True):
            seen.append((b, m, j, k, b["method"][j:k].tolist()))
            return real_obj(m, j, k, b, errs, metas, direct)

        def spy_win(b, s0, s1):
            # a window of a run's group (_columnar_run): a chunk as cut
            seen.append((b, int(b["method"][s0]), s0, s1,
                         b["method"][s0:s1].tolist()))
            return real_win(b, s0, s1)

        real_win = sp._col_window
        sp._columnar_chunk, sp._object_chunk = spy_col, spy_obj
        sp._col_window = spy_win
        sizes = [(METHOD_GET_PEER_RATE_LIMITS, 10),
                 (METHOD_GET_PEER_RATE_LIMITS, 10),
                 (METHOD_GET_RATE_LIMITS, 5),
                 (METHOD_GET_PEER_RATE_LIMITS, 37),
                 (METHOD_GET_RATE_LIMITS, 20)]
        frames = [[RateLimitReq(name="cc", unique_key=f"f{f}k{i}", hits=1,
                                limit=4, duration=60_000) for i in range(n)]
                  for f, (_m, n) in enumerate(sizes)]
        pulls = []
        try:
            got = send_as_one_pull(sp, cp, frames,
                                   methods=[m for m, _n in sizes],
                                   pulls=pulls)
            for (_m, n), out in zip(sizes, got):
                assert [(r.error, r.remaining) for r in out] == [("", 3)] * n
            for ctx, n_pull, methods in pulls:
                at = 0
                for owner, m, j, k, inside in seen:
                    if owner is not ctx and owner is not ctx.b:
                        continue
                    assert j == at and inside == [m] * (k - j)
                    assert 0 < k - j <= cap
                    assert (k == n_pull or methods[k] != m or k - j == cap)
                    at = k
                assert at == n_pull
        finally:
            cp.close()
            sp.close()
            ip.close()


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

"""Engine tests: duplicate-key rounds, directory recycling, Store/Loader SPI.

Mirrors the reference's persistence tests (reference: store_test.go:30-245)
and the mutex-serialized same-key semantics (reference: gubernator.go:328).
"""

import random

import pytest

from gubernator_tpu.models import Engine, KeyDirectory
from gubernator_tpu.ops.oracle import oracle_decide
from gubernator_tpu.store import BucketSnapshot, MockLoader, MockStore
from gubernator_tpu.types import Algorithm, Behavior, RateLimitReq, Status

# Far-future epoch: snapshot()/close() compare expiry against the real
# clock, so simulated "now" must sort after wall time.
NOW = 2_000_000_000_000


def req(key="k", name="test", hits=1, limit=10, duration=60_000, algorithm=0, behavior=0):
    return RateLimitReq(name=name, unique_key=key, hits=hits, limit=limit,
                        duration=duration, algorithm=algorithm, behavior=behavior)


@pytest.fixture(scope="module")
def engine():
    # module-scoped: one compile, tests use distinct key names
    return Engine(capacity=256, min_width=8, max_width=64)


class TestEngineBasics:
    def test_single(self, engine):
        rs = engine.get_rate_limits([req(key="b1", hits=1)], now_ms=NOW)
        assert rs[0].status == Status.UNDER_LIMIT
        assert rs[0].remaining == 9
        assert rs[0].reset_time == NOW + 60_000

    def test_validation_errors(self, engine):
        rs = engine.get_rate_limits(
            [RateLimitReq(name="", unique_key="x"),
             RateLimitReq(name="x", unique_key=""),
             req(key="b2")],
            now_ms=NOW)
        assert rs[0].error == "field 'namespace' cannot be empty"
        assert rs[1].error == "field 'unique_key' cannot be empty"
        assert rs[2].error == ""

    def test_invalid_gregorian(self, engine):
        rs = engine.get_rate_limits(
            [req(key="b3", duration=99, behavior=Behavior.DURATION_IS_GREGORIAN)],
            now_ms=NOW)
        assert "gregorian" in rs[0].error

    def test_duplicate_keys_serialize(self, engine):
        # 5 hits of 3 against limit 10: two succeed, rest rejected at rem=4
        # without deducting — matches mutex-serialized reference behavior
        rs = engine.get_rate_limits([req(key="dup", hits=3) for _ in range(5)],
                                    now_ms=NOW)
        stats = [r.status for r in rs]
        rems = [r.remaining for r in rs]
        assert stats == [0, 0, 0, 1, 1]
        assert rems == [7, 4, 1, 1, 1]

    def test_duplicate_mixed_order_preserved(self, engine):
        rs = engine.get_rate_limits(
            [req(key="dm", hits=8), req(key="dm", hits=4), req(key="dm", hits=2)],
            now_ms=NOW)
        assert [r.status for r in rs] == [0, 1, 0]
        assert [r.remaining for r in rs] == [2, 2, 0]

    def test_large_batch_spans_chunks(self, engine):
        n = 150  # > max_width=64 -> 3 chunks
        rs = engine.get_rate_limits([req(key=f"lb{i}") for i in range(n)], now_ms=NOW)
        assert all(r.status == Status.UNDER_LIMIT and r.remaining == 9 for r in rs)

    def test_gregorian_duration(self, engine):
        from gubernator_tpu.utils.gregorian import gregorian_expiration
        import datetime as dt
        rs = engine.get_rate_limits(
            [req(key="greg", duration=0, behavior=Behavior.DURATION_IS_GREGORIAN)],
            now_ms=NOW)
        want = gregorian_expiration(dt.datetime.fromtimestamp(NOW / 1000.0), 0)
        assert rs[0].reset_time == want
        assert rs[0].remaining == 9


class TestDirectoryRecycling:
    def test_eviction_recycles_slots(self):
        eng = Engine(capacity=8, min_width=8, max_width=8)
        for i in range(8):
            eng.get_rate_limits([req(key=f"k{i}")], now_ms=NOW)
        assert len(eng.directory) == 8
        # ninth key evicts the LRU (k0); k0 re-added later starts fresh
        eng.get_rate_limits([req(key="k8")], now_ms=NOW + 1)
        assert eng.directory.evictions == 1
        rs = eng.get_rate_limits([req(key="k0", hits=1)], now_ms=NOW + 2)
        assert rs[0].remaining == 9  # state was lost with the slot

    def test_directory_lru_order(self):
        d = KeyDirectory(2)
        s, f = d.lookup(["a", "b"])
        assert f == [True, True]
        d.lookup(["a"])  # refresh a
        d.lookup(["c"])  # evicts b
        assert "b" not in d and "a" in d and "c" in d
        assert d.evictions == 1

    def test_duplicate_in_one_lookup_shares_slot(self):
        d = KeyDirectory(4)
        s, f = d.lookup(["x", "x", "y"])
        assert s[0] == s[1] != s[2]
        assert f == [True, False, True]

    def test_same_call_keys_are_pinned_against_eviction(self):
        # capacity-many distinct keys in one lookup must get distinct slots
        # even when eviction kicks in (collision-free scatter invariant)
        d = KeyDirectory(4)
        d.lookup(["old1", "old2"])
        s, f = d.lookup(["a", "b", "c", "d"])
        assert len(set(s)) == 4
        assert d.evictions == 2  # old1/old2 recycled, never a/b/c/d

    def test_over_committed_lookup_raises(self):
        d = KeyDirectory(2)
        with pytest.raises(RuntimeError):
            d.lookup(["a", "b", "c"])

    def test_engine_chunk_exceeding_capacity_stays_correct(self):
        # 16 distinct keys through a capacity-8 engine in ONE call: chunking
        # clamps rounds to capacity; every response is a valid fresh decision
        eng = Engine(capacity=8, min_width=8, max_width=64)
        rs = eng.get_rate_limits(
            [req(key=f"cc{i}") for i in range(16)], now_ms=NOW)
        assert all(r.status == Status.UNDER_LIMIT and r.remaining == 9
                   for r in rs)
        assert eng.directory.evictions == 8


class TestStoreSPI:
    def test_read_through_and_write_through(self):
        store = MockStore()
        eng = Engine(capacity=32, min_width=8, max_width=32, store=store)
        eng.get_rate_limits([req(key="s1", hits=1)], now_ms=NOW)
        # miss -> get; decision -> on_change
        assert store.called["get"] == 1
        assert store.called["on_change"] == 1
        snap = store.data["test_s1"]
        assert snap.remaining == 9 and snap.algo == Algorithm.TOKEN_BUCKET
        # hit: no second get
        eng.get_rate_limits([req(key="s1", hits=2)], now_ms=NOW + 1)
        assert store.called["get"] == 1
        assert store.data["test_s1"].remaining == 7

    def test_read_through_restores_state(self):
        store = MockStore()
        store.data["test_s2"] = BucketSnapshot(
            key="test_s2", algo=0, limit=10, remaining=3, duration=60_000,
            stamp=NOW - 1000, expire_at=NOW + 59_000)
        eng = Engine(capacity=32, min_width=8, max_width=32, store=store)
        rs = eng.get_rate_limits([req(key="s2", hits=1)], now_ms=NOW)
        assert rs[0].remaining == 2
        assert store.called["get"] == 1

    def test_reset_remaining_removes(self):
        store = MockStore()
        eng = Engine(capacity=32, min_width=8, max_width=32, store=store)
        eng.get_rate_limits([req(key="s3", hits=1)], now_ms=NOW)
        eng.get_rate_limits(
            [req(key="s3", behavior=Behavior.RESET_REMAINING)], now_ms=NOW + 1)
        assert store.called["remove"] == 1
        assert "test_s3" not in store.data

    def test_algorithm_switch_removes_then_recreates(self):
        store = MockStore()
        eng = Engine(capacity=32, min_width=8, max_width=32, store=store)
        eng.get_rate_limits([req(key="s4", hits=1)], now_ms=NOW)
        rs = eng.get_rate_limits(
            [req(key="s4", hits=1, algorithm=Algorithm.LEAKY_BUCKET)], now_ms=NOW + 1)
        assert store.called["remove"] == 1
        assert rs[0].remaining == 9
        assert store.data["test_s4"].algo == Algorithm.LEAKY_BUCKET


class TestLoaderSPI:
    def test_load_and_save_roundtrip(self):
        loader = MockLoader([
            BucketSnapshot(key="test_l1", algo=0, limit=10, remaining=4,
                           duration=60_000, stamp=NOW - 1000,
                           expire_at=NOW + 59_000),
        ])
        eng = Engine(capacity=32, min_width=8, max_width=32, loader=loader)
        assert loader.called["load"] == 1
        rs = eng.get_rate_limits([req(key="l1", hits=1)], now_ms=NOW)
        assert rs[0].remaining == 3
        eng.close()
        assert loader.called["save"] == 1
        saved = {s.key: s for s in loader.contents}
        assert saved["test_l1"].remaining == 3

    def test_save_skips_expired(self):
        loader = MockLoader()
        eng = Engine(capacity=32, min_width=8, max_width=32, loader=loader)
        eng.get_rate_limits([req(key="l2", duration=1)], now_ms=1_000)  # long expired
        eng.get_rate_limits([req(key="l3", duration=10**12)], now_ms=NOW)
        eng.close()
        keys = {s.key for s in loader.contents}
        assert "test_l3" in keys and "test_l2" not in keys


class TestEngineMatchesOracleWithDuplicates:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_fuzz_with_duplicates(self, seed):
        rng = random.Random(seed)
        eng = Engine(capacity=64, min_width=8, max_width=32)
        oracle_table = {}
        now = NOW
        keys = [f"f{i}" for i in range(6)]
        for _ in range(40):
            now += rng.randint(0, 2000)
            batch = []
            for _ in range(rng.randint(1, 10)):
                k = rng.choice(keys)
                batch.append(req(
                    key=k,
                    hits=rng.choice([0, 1, 2, 5]),
                    limit=rng.choice([3, 10]),
                    duration=rng.choice([1000, 60_000]),
                    algorithm=rng.choice([0, 1]),
                ))
            got = eng.get_rate_limits(batch, now_ms=now)
            for r, g in zip(batch, got):
                want = oracle_decide(
                    oracle_table, r.hash_key(), hits=r.hits, limit=r.limit,
                    duration=r.duration, algorithm=r.algorithm,
                    behavior=r.behavior, now=now)
                assert (g.status, g.limit, g.remaining, g.reset_time) == (
                    want.status, want.limit, want.remaining, want.reset_time)


class TestFileLoader:
    """FileLoader: durable JSON-lines snapshots (past-the-reference; the
    reference ships only mocks, store.go:60-130)."""

    def test_roundtrip_through_engine_restart(self, tmp_path):
        from gubernator_tpu.store import FileLoader

        from gubernator_tpu.utils.interval import millisecond_now

        path = str(tmp_path / "snap" / "buckets.jsonl")
        # snapshot() filters rows expired against the wall clock, so the
        # pinned timestamps must be near real now
        now = millisecond_now()

        eng = Engine(capacity=64, min_width=8, max_width=32,
                     loader=FileLoader(path))
        rs = eng.get_rate_limits(
            [RateLimitReq(name="f", unique_key=f"k{i}", hits=2, limit=10,
                          duration=3_600_000) for i in range(5)],
            now_ms=now,
        )
        assert all(r.remaining == 8 for r in rs)
        eng.close()  # saves the snapshot

        # a fresh engine resumes the drained state
        eng2 = Engine(capacity=64, min_width=8, max_width=32,
                      loader=FileLoader(path))
        rs = eng2.get_rate_limits(
            [RateLimitReq(name="f", unique_key=f"k{i}", hits=1, limit=10,
                          duration=3_600_000) for i in range(5)],
            now_ms=now + 1000,
        )
        assert all(r.remaining == 7 for r in rs), [r.remaining for r in rs]

    def test_missing_file_loads_empty(self, tmp_path):
        from gubernator_tpu.store import FileLoader

        assert list(FileLoader(str(tmp_path / "nope.jsonl")).load()) == []

    def test_corrupt_rows_are_skipped(self, tmp_path):
        from gubernator_tpu.store import BucketSnapshot, FileLoader

        path = str(tmp_path / "b.jsonl")
        fl = FileLoader(path)
        fl.save([BucketSnapshot(key="a_b", algo=0, limit=5, remaining=3,
                                duration=1000, stamp=1, expire_at=2)])
        with open(path, "a", encoding="utf-8") as f:
            f.write('{"not": "a snapshot"}\n')   # schema drift
            f.write('{"key": "trunc')            # truncated tail
        rows = list(fl.load())
        assert [r.key for r in rows] == ["a_b"]

    def test_atomic_save_leaves_no_tmp(self, tmp_path):
        from gubernator_tpu.store import BucketSnapshot, FileLoader

        path = str(tmp_path / "b.jsonl")
        fl = FileLoader(path)
        fl.save([BucketSnapshot(key="a_b", algo=0, limit=5, remaining=3,
                                duration=1000, stamp=1, expire_at=2)])
        import os
        assert not os.path.exists(path + ".tmp")
        [snap] = fl.load()
        assert snap.key == "a_b" and snap.remaining == 3


class TestScannedRounds:
    """The multi-round scan fast-path must be indistinguishable from the
    one-dispatch-per-round path (same mutex-serialized semantics,
    reference: gubernator.go:328)."""

    def test_hot_key_herd_exact_semantics(self):
        # 100 duplicates of one key = 100 rounds -> 4 scan groups of <=32
        eng = Engine(capacity=2048, min_width=8, max_width=64)
        reqs = [req(key="herd", hits=1, limit=50) for _ in range(100)]
        rs = eng.get_rate_limits(reqs, now_ms=NOW)
        assert [r.status for r in rs[:50]] == [Status.UNDER_LIMIT] * 50
        assert [r.status for r in rs[50:]] == [Status.OVER_LIMIT] * 50
        assert [r.remaining for r in rs[:50]] == list(range(49, -1, -1))
        assert all(r.remaining == 0 for r in rs[50:])

    def test_scan_path_matches_per_round_path(self):
        rnd = random.Random(7)
        keys = [f"sc{i}" for i in range(12)]

        def batch():
            return [req(key=rnd.choice(keys), hits=rnd.randint(0, 4),
                        limit=10, duration=60_000,
                        algorithm=rnd.choice([0, 1]))
                    for _ in range(rnd.randint(2, 40))]

        batches = [batch() for _ in range(6)]
        big = Engine(capacity=2048, min_width=8, max_width=64)   # scans
        small = Engine(capacity=64, min_width=8, max_width=64)
        small._split_scannable = lambda windows: (windows, [])   # per-round
        assert Engine(capacity=64, min_width=8, max_width=64)._split_scannable(
            [[None] * 20, [None] * 20]) == ([[None] * 20, [None] * 20], [])
        for k, b in enumerate(batches):
            got = big.get_rate_limits(b, now_ms=NOW + k * 1000)
            want = small.get_rate_limits(b, now_ms=NOW + k * 1000)
            assert got == want

    def test_store_rides_scan_with_batched_hooks(self):
        """VERDICT r2 item 5: a Store no longer disables scan dispatch —
        the hooks batch to ONE read-through before the tail and ONE
        write-through after it with the key's final row (the reference
        pays one OnChange per hit, algorithms.go:64-68; PARITY #8)."""
        store = MockStore()
        eng = Engine(capacity=2048, min_width=8, max_width=64, store=store)
        rounds_before = eng.stats.rounds
        rs = eng.get_rate_limits([req(key="sd", hits=2, limit=10)
                                  for _ in range(4)], now_ms=NOW)
        assert [r.remaining for r in rs] == [8, 6, 4, 2]
        # 4 duplicate rounds retired in ONE scan dispatch, not 4
        assert eng.stats.rounds - rounds_before == 4
        assert eng.stats.stage_ns["device"] > 0
        # one get (miss) + one batched on_change with the FINAL state
        assert store.called["get"] == 1
        assert store.called["on_change"] == 1
        assert store.data["test_sd"].remaining == 2

    def test_store_scan_chunked_round0_keeps_fresh_flags(self):
        """Round 0 chunked at max_width puts FIRST-occurrence keys in a
        later tail window; the union pre-lookup must not strip their
        fresh flags (a recycled slot's stale device row would decide), and
        later-round duplicates must still pack as live."""
        store = MockStore()
        eng = Engine(capacity=2048, min_width=16, max_width=16, store=store)
        # 20 distinct never-seen keys, 4 of them twice ->
        # rounds [20 -> chunks 16+4, 4]: tail = [16, 4, 4]
        reqs = [req(key=f"cf{i}", hits=2, limit=10) for i in range(20)]
        reqs += [req(key=f"cf{i}", hits=3, limit=10) for i in range(4)]
        rs = eng.get_rate_limits(reqs, now_ms=NOW)
        assert [r.remaining for r in rs[:20]] == [8] * 20  # all fresh
        assert [r.remaining for r in rs[20:]] == [5] * 4  # sequential
        # final rows persisted once per key
        assert store.data["test_cf19"].remaining == 8
        assert store.data["test_cf0"].remaining == 5

    def test_store_scan_read_through_restores(self):
        """Keys missing from the table but present in the store must be
        injected before the scan tail decides them."""
        store = MockStore()
        store.data["test_sr"] = BucketSnapshot(
            key="test_sr", algo=0, limit=10, remaining=3, duration=60_000,
            stamp=NOW - 1000, expire_at=NOW + 59_000)
        eng = Engine(capacity=2048, min_width=8, max_width=64, store=store)
        rs = eng.get_rate_limits([req(key="sr", hits=1, limit=10)
                                  for _ in range(3)], now_ms=NOW)
        # resumes from remaining=3, not a fresh bucket
        assert [r.remaining for r in rs] == [2, 1, 0]
        assert store.data["test_sr"].remaining == 0

    def test_herd_33_singleton_group(self):
        # 33 windows -> scan groups [32, 1]; the singleton takes the
        # per-round program (warmup never compiles scan depth 1)
        eng = Engine(capacity=2048, min_width=8, max_width=64)
        rs = eng.get_rate_limits(
            [req(key="h33", hits=1, limit=20) for _ in range(33)], now_ms=NOW)
        assert [r.status for r in rs] == [0] * 20 + [1] * 13
        assert rs[32].remaining == 0


class TestStageClocks:
    """Per-stage wall-clock breakdown (tracing tier; the reference has no
    latency observability beyond RPC histograms, SURVEY §5.1)."""

    def test_stages_accumulate_on_both_paths(self):
        eng = Engine(capacity=2048, min_width=8, max_width=64)
        # per-round path (distinct keys) ...
        eng.get_rate_limits([req(key=f"t{i}") for i in range(10)], now_ms=NOW)
        # ... and the scan path (hot-key rounds)
        eng.get_rate_limits([req(key="hot") for _ in range(8)], now_ms=NOW)
        d = eng.stats.as_dict()
        for stage in ("prep", "lookup", "pack", "device", "demux"):
            assert d[f"{stage}_ns"] > 0, stage
        # device dominates on any real backend; sanity: all clocks are
        # bounded by a second for two tiny batches
        assert sum(d[f"{s}_ns"] for s in
                   ("prep", "lookup", "pack", "device", "demux")) < 60e9
        assert d["store_ns"] == 0  # no Store configured

    def test_store_stage_accumulates(self):
        eng = Engine(capacity=256, min_width=8, max_width=32,
                     store=MockStore())
        eng.get_rate_limits([req(key="st1")], now_ms=NOW)
        assert eng.stats.as_dict()["store_ns"] > 0


class TestNativeFastWindow:
    """The native one-pass window prep (native/keydir.cpp
    keydir_prep_pack_fast) must be response-identical to the python
    pipeline, including its leftover routing for duplicate, gregorian, and
    invalid lanes."""

    def _engines(self):
        import gubernator_tpu.native as native

        fast = Engine(capacity=128, min_width=8, max_width=64)
        if fast._prep_fast is None:
            pytest.skip("native prep unavailable")
        slow = Engine(capacity=128, min_width=8, max_width=64)
        slow._prep_fast = None  # force the python pipeline
        assert isinstance(fast.directory, native.NativeKeyDirectory)
        return fast, slow

    def test_greg_lane_blocks_later_same_key_occurrence(self):
        """Per-key order: a gregorian lane (leftover) must drag its key's
        LATER plain occurrence into the leftovers too — otherwise the plain
        hit would apply before the gregorian one."""
        fast, slow = self._engines()
        batch = [
            req(key="ord", behavior=Behavior.DURATION_IS_GREGORIAN,
                duration=1, hits=2),  # 1 = minutes
            req(key="ord", hits=3),   # must observe the gregorian hit first
        ]
        a = fast.get_rate_limits(batch, now_ms=NOW)
        b = slow.get_rate_limits(batch, now_ms=NOW)
        assert a == b
        assert a[1].remaining == 5  # 10 - 2 - 3, sequential

    def test_differential_mixed_lanes(self):
        """Randomized windows mixing plain, duplicate, gregorian, invalid,
        and hits=0 lanes: fast and python engines must agree exactly."""
        fast, slow = self._engines()
        rng = random.Random(11)
        now = NOW
        for step in range(30):
            now += rng.randint(0, 2000)
            batch = []
            for _ in range(rng.randint(1, 24)):
                kind = rng.random()
                if kind < 0.08:
                    batch.append(req(key="", hits=1))  # invalid
                elif kind < 0.2:
                    batch.append(req(
                        key=f"g{rng.randint(0, 2)}", hits=rng.randint(0, 2),
                        duration=rng.choice([0, 1]),  # minutes/hours codes
                        behavior=Behavior.DURATION_IS_GREGORIAN))
                else:
                    batch.append(req(
                        key=f"k{rng.randint(0, 9)}",
                        hits=rng.randint(0, 3),
                        limit=rng.choice([5, 10]),
                        algorithm=rng.choice(
                            [Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]),
                        behavior=rng.choice(
                            [0, int(Behavior.RESET_REMAINING)])))
            a = fast.get_rate_limits(batch, now_ms=now)
            b = slow.get_rate_limits(batch, now_ms=now)
            assert a == b, f"divergence at step {step}"

    def test_stats_attribution(self):
        fast, _ = self._engines()
        fast.get_rate_limits([req(key=f"s{i}") for i in range(10)],
                             now_ms=NOW)
        s = fast.stats.as_dict()
        assert s["requests"] == 10 and s["rounds"] == 1
        assert s["prep_ns"] > 0 and s["device_ns"] > 0
        assert s["lookup_ns"] == 0 and s["pack_ns"] == 0  # folded into prep

    def test_batches_counted_once_with_leftovers(self):
        fast, _ = self._engines()
        fast.get_rate_limits(
            [req(key="bc"), req(key="bc")], now_ms=NOW)  # dup -> tail
        assert fast.stats.batches == 1
        assert fast.stats.requests == 2


class TestStagingAutoSelect:
    """The engine ships each window in the compact wire format whenever it
    is eligible and falls back to wide otherwise (VERDICT r3 item 1);
    GUBER_STAGING=wide pins the wide format. Observable via the dispatch
    helper's handle: compact handles carry their now_ms."""

    def test_compact_selected_for_eligible_window(self):
        import numpy as np
        eng = Engine(capacity=64, min_width=8, max_width=8)
        packed = np.zeros((9, 8), np.int64)
        packed[0] = [0, 1, 2, -1, -1, -1, -1, -1]
        packed[1:4, :3] = [[1] * 3, [10] * 3, [60_000] * 3]
        handle = eng._dispatch_staged(packed, NOW)
        assert handle[1] == NOW  # compact: handle carries now_ms
        out, nbytes = eng._fetch_staged(handle)
        assert nbytes == 4 * 8 * 4  # the compact i32[4, 8] as it came back
        assert out.dtype == np.int64 and out.shape == (4, 8)
        assert out[3, 0] == NOW + 60_000  # widened back to absolute

    def test_wide_kept_for_gregorian(self):
        import numpy as np
        eng = Engine(capacity=64, min_width=8, max_width=8)
        packed = np.zeros((9, 8), np.int64)
        packed[0] = [0, -1, -1, -1, -1, -1, -1, -1]
        packed[1:4, 0] = [1, 10, 60_000]
        packed[5, 0] = int(Behavior.DURATION_IS_GREGORIAN)
        packed[6, 0] = NOW + 3_600_000
        packed[7, 0] = 3_600_000
        handle = eng._dispatch_staged(packed, NOW)
        assert handle[1] is None  # wide path

    def test_env_pin_wide(self, monkeypatch):
        import numpy as np
        monkeypatch.setenv("GUBER_STAGING", "wide")
        eng = Engine(capacity=64, min_width=8, max_width=8)
        packed = np.zeros((9, 8), np.int64)
        packed[0] = -1
        handle = eng._dispatch_staged(packed, NOW)
        assert handle[1] is None

    def test_responses_identical_across_modes(self, monkeypatch):
        rng = random.Random(5)
        keys = [f"sas{i}" for i in range(40)]

        def traffic(e):
            out = []
            for step in range(6):
                batch = [req(key=rng.choice(keys), hits=rng.randint(0, 3),
                             limit=20, duration=60_000,
                             algorithm=rng.randint(0, 1))
                         for _ in range(rng.randint(1, 30))]
                out.append(e.get_rate_limits(batch, now_ms=NOW + step * 500))
            return out
        rng_state = rng.getstate()
        auto = Engine(capacity=128, min_width=8, max_width=32)
        a = traffic(auto)
        monkeypatch.setenv("GUBER_STAGING", "wide")
        rng.setstate(rng_state)
        wide = Engine(capacity=128, min_width=8, max_width=32)
        b = traffic(wide)
        assert a == b

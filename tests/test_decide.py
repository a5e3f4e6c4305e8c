"""Decision-kernel tests: scenario ports of the reference's functional tests
plus randomized kernel-vs-oracle equivalence.

Scenario sources: token bucket sequences (reference: functional_test.go:51-148),
leaky bucket drain (:150-209), config hot-change (:347-433), RESET_REMAINING
(:435-505). Times are simulated — the kernel takes `now` as an input, so no
sleeps are needed (the reference sleeps real wall-clock).
"""

import random

import jax
import numpy as np
import pytest

from gubernator_tpu.ops import decide, make_table
from gubernator_tpu.ops.decide import (
    batch_from_columns,
    fetch_rows,
    host_rows,
)
from gubernator_tpu.ops.oracle import oracle_decide
from gubernator_tpu.types import Algorithm, Behavior, Status

# One shared compiled kernel at a fixed padded batch width: eager-mode
# per-primitive CPU compiles are pathologically slow, and prod always runs
# jitted at bucketed widths anyway.
_PAD = 8
_DECIDE = jax.jit(decide)


def padded_batch(cols):
    n = len(cols["slot"])
    pad = _PAD * ((n + _PAD - 1) // _PAD) - n
    return batch_from_columns(
        cols["slot"] + [-1] * pad,
        cols["hits"] + [0] * pad,
        cols["limit"] + [0] * pad,
        cols["duration"] + [0] * pad,
        cols["algorithm"] + [0] * pad,
        cols["behavior"] + [0] * pad,
        cols["greg_expire"] + [0] * pad,
        cols["greg_interval"] + [0] * pad,
        cols["fresh"] + [False] * pad,
    )


class Harness:
    """Single-key-at-a-time harness: host slot directory over the kernel."""

    def __init__(self, capacity=64):
        self.state = make_table(capacity)
        self.dir = {}

    def hit(self, key, *, hits, limit, duration, algorithm=Algorithm.TOKEN_BUCKET,
            behavior=0, now=0, greg_expire=0, greg_interval=0):
        fresh = key not in self.dir
        if fresh:
            self.dir[key] = len(self.dir)
        slot = self.dir[key]
        reqs = padded_batch(dict(
            slot=[slot], hits=[hits], limit=[limit], duration=[duration],
            algorithm=[int(algorithm)], behavior=[int(behavior)],
            greg_expire=[greg_expire], greg_interval=[greg_interval],
            fresh=[fresh],
        ))
        self.state, resp = _DECIDE(self.state, reqs, now)
        return (
            int(resp.status[0]),
            int(resp.limit[0]),
            int(resp.remaining[0]),
            int(resp.reset_time[0]),
        )


class TestTokenBucket:
    def test_over_limit_sequence(self):
        h = Harness()
        now = 1_000_000
        # limit 2 per 1s window: hit, hit, reject (functional_test.go:51-96)
        assert h.hit("a", hits=1, limit=2, duration=1000, now=now) == (
            Status.UNDER_LIMIT, 2, 1, now + 1000)
        assert h.hit("a", hits=1, limit=2, duration=1000, now=now + 10)[:3] == (
            Status.UNDER_LIMIT, 2, 0)
        st, _, rem, _ = h.hit("a", hits=1, limit=2, duration=1000, now=now + 20)
        assert (st, rem) == (Status.OVER_LIMIT, 0)
        # after the window expires, the bucket refills
        st, _, rem, reset = h.hit("a", hits=1, limit=2, duration=1000, now=now + 2000)
        assert (st, rem, reset) == (Status.UNDER_LIMIT, 1, now + 3000)

    def test_remaining_refill_on_new_window(self):
        h = Harness()
        now = 5_000_000
        for i in range(5):
            st, _, rem, _ = h.hit("k", hits=1, limit=5, duration=1000, now=now + i)
            assert st == Status.UNDER_LIMIT
            assert rem == 4 - i
        st, *_ = h.hit("k", hits=1, limit=5, duration=1000, now=now + 10)
        assert st == Status.OVER_LIMIT

    def test_sticky_over_limit_on_peek(self):
        h = Harness()
        now = 1_000_000
        h.hit("k", hits=1, limit=1, duration=60_000, now=now)
        st, *_ = h.hit("k", hits=1, limit=1, duration=60_000, now=now + 1)
        assert st == Status.OVER_LIMIT
        # hits=0 peek reports the stored OVER_LIMIT (algorithms.go:107-115)
        st, _, rem, _ = h.hit("k", hits=0, limit=1, duration=60_000, now=now + 2)
        assert (st, rem) == (Status.OVER_LIMIT, 0)

    def test_over_request_does_not_deduct(self):
        h = Harness()
        now = 1_000_000
        h.hit("k", hits=10, limit=100, duration=60_000, now=now)
        st, _, rem, _ = h.hit("k", hits=1000, limit=100, duration=60_000, now=now + 1)
        assert (st, rem) == (Status.OVER_LIMIT, 90)
        st, _, rem, _ = h.hit("k", hits=90, limit=100, duration=60_000, now=now + 2)
        assert (st, rem) == (Status.UNDER_LIMIT, 0)

    def test_first_request_over_limit(self):
        h = Harness()
        st, _, rem, _ = h.hit("k", hits=1000, limit=100, duration=60_000, now=1_000)
        # rejected but stored undrained (algorithms.go:160-165)
        assert (st, rem) == (Status.OVER_LIMIT, 100)
        st, _, rem, _ = h.hit("k", hits=100, limit=100, duration=60_000, now=1_001)
        assert (st, rem) == (Status.UNDER_LIMIT, 0)

    def test_limit_hot_change(self):
        h = Harness()
        now = 1_000_000
        h.hit("k", hits=1, limit=10, duration=60_000, now=now)
        # raise limit: remaining preserved (functional_test.go:347-433)
        st, lim, rem, _ = h.hit("k", hits=1, limit=20, duration=60_000, now=now + 1)
        assert (st, lim, rem) == (Status.UNDER_LIMIT, 20, 8)
        # lower limit below remaining: clamps
        st, lim, rem, _ = h.hit("k", hits=1, limit=5, duration=60_000, now=now + 2)
        assert (st, lim, rem) == (Status.UNDER_LIMIT, 5, 4)

    def test_duration_hot_change(self):
        h = Harness()
        now = 1_000_000
        _, _, _, reset0 = h.hit("k", hits=1, limit=10, duration=10_000, now=now)
        assert reset0 == now + 10_000
        # lengthen: new expiry anchored at CreatedAt (algorithms.go:86-104)
        _, _, _, reset1 = h.hit("k", hits=1, limit=10, duration=60_000, now=now + 100)
        assert reset1 == now + 60_000
        # shrink so the bucket is already expired: recreated fresh
        st, _, rem, reset2 = h.hit("k", hits=1, limit=10, duration=50, now=now + 100)
        assert (st, rem, reset2) == (Status.UNDER_LIMIT, 9, now + 150)

    def test_reset_remaining(self):
        h = Harness()
        now = 1_000_000
        h.hit("k", hits=10, limit=10, duration=60_000, now=now)
        st, *_ = h.hit("k", hits=1, limit=10, duration=60_000, now=now + 1)
        assert st == Status.OVER_LIMIT
        st, _, rem, reset = h.hit(
            "k", hits=1, limit=10, duration=60_000,
            behavior=Behavior.RESET_REMAINING, now=now + 2)
        assert (st, rem, reset) == (Status.UNDER_LIMIT, 10, 0)
        # bucket was deleted; next request recreates
        st, _, rem, _ = h.hit("k", hits=4, limit=10, duration=60_000, now=now + 3)
        assert (st, rem) == (Status.UNDER_LIMIT, 6)

    def test_algorithm_switch_resets(self):
        h = Harness()
        now = 1_000_000
        h.hit("k", hits=5, limit=10, duration=60_000, now=now)
        st, _, rem, _ = h.hit(
            "k", hits=1, limit=10, duration=60_000,
            algorithm=Algorithm.LEAKY_BUCKET, now=now + 1)
        assert (st, rem) == (Status.UNDER_LIMIT, 9)

    def test_expired_bucket_recreated(self):
        h = Harness()
        h.hit("k", hits=10, limit=10, duration=1000, now=1_000)
        st, _, rem, _ = h.hit("k", hits=1, limit=10, duration=1000, now=10_000)
        assert (st, rem) == (Status.UNDER_LIMIT, 9)


class TestLeakyBucket:
    def test_drain(self):
        h = Harness()
        now = 1_000_000
        # limit 10 per 10s -> 1 token leaks back per second
        for i in range(10):
            st, _, rem, _ = h.hit("k", hits=1, limit=10, duration=10_000,
                                  algorithm=Algorithm.LEAKY_BUCKET, now=now)
            assert st == Status.UNDER_LIMIT
            assert rem == 9 - i
        st, *_ = h.hit("k", hits=1, limit=10, duration=10_000,
                       algorithm=Algorithm.LEAKY_BUCKET, now=now)
        assert st == Status.OVER_LIMIT
        # one rate period later exactly one token has leaked back
        st, _, rem, reset = h.hit("k", hits=1, limit=10, duration=10_000,
                                  algorithm=Algorithm.LEAKY_BUCKET, now=now + 1000)
        assert (st, rem, reset) == (Status.UNDER_LIMIT, 0, now + 2000)

    def test_full_refill_after_duration(self):
        h = Harness()
        now = 1_000_000
        for _ in range(10):
            h.hit("k", hits=1, limit=10, duration=10_000,
                  algorithm=Algorithm.LEAKY_BUCKET, now=now)
        st, _, rem, _ = h.hit("k", hits=1, limit=10, duration=10_000,
                              algorithm=Algorithm.LEAKY_BUCKET, now=now + 10_000)
        assert (st, rem) == (Status.UNDER_LIMIT, 9)

    def test_reset_remaining_refills(self):
        h = Harness()
        now = 1_000_000
        for _ in range(10):
            h.hit("k", hits=1, limit=10, duration=10_000,
                  algorithm=Algorithm.LEAKY_BUCKET, now=now)
        st, _, rem, _ = h.hit("k", hits=1, limit=10, duration=10_000,
                              algorithm=Algorithm.LEAKY_BUCKET,
                              behavior=Behavior.RESET_REMAINING, now=now + 1)
        # refilled to limit then the hit deducts (algorithms.go:205-207)
        assert (st, rem) == (Status.UNDER_LIMIT, 9)

    def test_over_request_no_deduct(self):
        h = Harness()
        now = 1_000_000
        h.hit("k", hits=2, limit=10, duration=10_000,
              algorithm=Algorithm.LEAKY_BUCKET, now=now)
        st, _, rem, _ = h.hit("k", hits=100, limit=10, duration=10_000,
                              algorithm=Algorithm.LEAKY_BUCKET, now=now + 1)
        assert (st, rem) == (Status.OVER_LIMIT, 8)

    def test_first_request_over_limit_empties(self):
        h = Harness()
        st, _, rem, _ = h.hit("k", hits=100, limit=10, duration=10_000,
                              algorithm=Algorithm.LEAKY_BUCKET, now=1_000)
        # stored empty, unlike token bucket (algorithms.go:319-323)
        assert (st, rem) == (Status.OVER_LIMIT, 0)

    def test_peek(self):
        h = Harness()
        now = 1_000_000
        h.hit("k", hits=3, limit=10, duration=10_000,
              algorithm=Algorithm.LEAKY_BUCKET, now=now)
        st, _, rem, _ = h.hit("k", hits=0, limit=10, duration=10_000,
                              algorithm=Algorithm.LEAKY_BUCKET, now=now)
        assert (st, rem) == (Status.UNDER_LIMIT, 7)


class TestKernelMatchesOracle:
    """Randomized equivalence: the batched kernel vs the sequential oracle."""

    @pytest.mark.parametrize("seed", range(6))
    def test_fuzz(self, seed):
        import datetime as dt

        from gubernator_tpu.utils.gregorian import (
            gregorian_duration,
            gregorian_expiration,
        )

        rng = random.Random(seed)
        keys = [f"k{i}" for i in range(10)]
        cap = 32
        state = make_table(cap)
        directory = {}
        oracle_table = {}
        now = 1_700_000_000_000

        for step in range(120):
            now += rng.randint(0, 3000)
            chosen = rng.sample(keys, rng.randint(1, 6))
            cols = {k: [] for k in (
                "slot hits limit duration algorithm behavior greg_expire "
                "greg_interval fresh".split())}
            params = []
            for key in chosen:
                fresh = key not in directory
                if fresh:
                    directory[key] = len(directory)
                behavior = 0
                if rng.random() < 0.1:
                    behavior |= Behavior.RESET_REMAINING
                duration = rng.choice([1000, 10_000, 60_000])
                ge = gi = 0
                if rng.random() < 0.25:
                    # gregorian: duration is a calendar code; feed the kernel
                    # the host-precomputed expiry/interval like the engine does
                    behavior |= Behavior.DURATION_IS_GREGORIAN
                    duration = rng.choice([0, 1, 2])  # minutes/hours/days
                    local = dt.datetime.fromtimestamp(now / 1000.0)
                    ge = gregorian_expiration(local, duration)
                    gi = gregorian_duration(local, duration)
                p = dict(
                    hits=rng.choice([0, 1, 1, 2, 5, 50]),
                    limit=rng.choice([1, 2, 10, 100]),
                    duration=duration,
                    algorithm=rng.choice([Algorithm.TOKEN_BUCKET, Algorithm.LEAKY_BUCKET]),
                    behavior=behavior,
                    greg_expire=ge,
                    greg_interval=gi,
                )
                params.append((key, p))
                cols["slot"].append(directory[key])
                cols["fresh"].append(fresh)
                for f in ("hits", "limit", "duration", "algorithm", "behavior",
                          "greg_expire", "greg_interval"):
                    cols[f].append(p[f])
            reqs = padded_batch(cols)
            state, resp = _DECIDE(state, reqs, now)
            for i, (key, p) in enumerate(params):
                want = oracle_decide(oracle_table, key, now=now, **p)
                got = (int(resp.status[i]), int(resp.limit[i]),
                       int(resp.remaining[i]), int(resp.reset_time[i]))
                assert got == (want.status, want.limit, want.remaining,
                               want.reset_time), f"step {step} key {key} {p}"

        # final state equivalence for live oracle rows
        for key, slot_idx in directory.items():
            row = oracle_table.get(key)
            if row is None or row.algo == -1:
                continue
            got = fetch_rows(state, [slot_idx])[0]
            assert int(got[0]) == row.algo, key
            assert int(got[2]) == row.remaining, key
            assert int(got[1]) == row.limit, key
            assert int(got[5]) == row.expire_at, key


class TestBatchMechanics:
    def test_padding_lanes_are_inert(self):
        state = make_table(8)
        reqs = padded_batch(dict(
            slot=[0, -1, -1], hits=[1, 99, 99], limit=[10, 99, 99],
            duration=[1000, 9, 9], algorithm=[0, 0, 0], behavior=[0, 0, 0],
            greg_expire=[0, 0, 0], greg_interval=[0, 0, 0],
            fresh=[True, False, False]))
        state, resp = _DECIDE(state, reqs, 1_000)
        assert int(resp.status[1]) == 0 and int(resp.remaining[1]) == 0
        assert int(host_rows(state)[1, 0]) == -1  # untouched
        assert int(host_rows(state)[0, 2]) == 9

    def test_padding_never_clobbers_last_slot(self):
        """-1 lanes must not wrap to slot capacity-1: jnp's mode="drop" only
        drops out-of-range-high indices, negatives wrap NumPy-style. A full
        table would otherwise lose its last bucket on every padded window."""
        state = make_table(8)
        # occupy the LAST slot with a live bucket
        occupy = padded_batch(dict(
            slot=[7], hits=[2], limit=[10], duration=[60_000],
            algorithm=[0], behavior=[0], greg_expire=[0], greg_interval=[0],
            fresh=[True]))
        state, _ = _DECIDE(state, occupy, 1_000)
        assert int(host_rows(state)[7, 2]) == 8
        # padded window touching a different slot; lanes 1-2 are padding
        win = padded_batch(dict(
            slot=[0, -1, -1], hits=[1, 0, 0], limit=[10, 0, 0],
            duration=[60_000, 0, 0], algorithm=[0, 0, 0], behavior=[0, 0, 0],
            greg_expire=[0, 0, 0], greg_interval=[0, 0, 0],
            fresh=[True, False, False]))
        state, _ = _DECIDE(state, win, 1_001)
        assert int(host_rows(state)[7, 0]) == 0
        assert int(host_rows(state)[7, 2]) == 8  # last slot survived

    def test_distinct_slots_parallel(self):
        state = make_table(64)
        n = 50
        reqs = padded_batch(dict(
            slot=list(range(n)), hits=[3] * n, limit=[10] * n,
            duration=[1000] * n, algorithm=[0] * n, behavior=[0] * n,
            greg_expire=[0] * n, greg_interval=[0] * n, fresh=[True] * n))
        state, resp = _DECIDE(state, reqs, 1_000)
        assert np.all(np.asarray(resp.remaining[:n]) == 7)
        assert np.all(host_rows(state)[:n, 2] == 7)


class TestScanPacked:
    """decide_scan_packed: K windows in one dispatch must equal K sequential
    decide_packed dispatches (same table writes, same responses)."""

    @pytest.mark.parametrize("seed", range(3))
    def test_matches_sequential(self, seed):
        from gubernator_tpu.ops.decide import decide_packed, decide_scan_packed

        r = random.Random(seed)
        rng = np.random.RandomState(seed)
        C, K, B, now = 128, 5, 16, 1_000_000

        def rand_packed():
            p = np.zeros((9, B), np.int64)
            n = r.randint(1, B)
            p[0, :n] = rng.choice(C, n, replace=False)
            p[0, n:] = -1
            p[1, :n] = rng.randint(0, 6, n)
            p[2, :n] = rng.randint(1, 20, n)
            p[3, :n] = rng.randint(500, 5000, n)
            p[4, :n] = rng.randint(0, 2, n)
            return p

        windows = [rand_packed() for _ in range(K)]

        # scan applies every window at one `now`; run sequential the same way
        step = jax.jit(decide_packed)
        seq_state2 = make_table(C)
        seq_outs2 = []
        for p in windows:
            seq_state2, out = step(seq_state2, p, now)
            seq_outs2.append(np.asarray(out))

        scan_state, scan_out = jax.jit(decide_scan_packed)(
            make_table(C), np.stack(windows), now)
        scan_out = np.asarray(scan_out)

        for k in range(K):
            np.testing.assert_array_equal(scan_out[k], seq_outs2[k])
        for col_seq, col_scan in zip(seq_state2, scan_state):
            np.testing.assert_array_equal(np.asarray(col_seq),
                                          np.asarray(col_scan))


class TestDocumentedReferenceBugFixes:
    """Pin the deliberate deviations from reference quirks (PARITY.md
    #2a-2c): each is a place where kernel AND oracle intentionally differ
    from algorithms.go, so the differential suite alone can't prove the
    behavior — these tests do."""

    def test_leaky_deduction_extends_expiry_sanely(self):
        """PARITY #2a: after a leaky deduction, expire_at = now + duration —
        not the reference's now*duration (algorithms.go:287)."""
        h = Harness(capacity=8)
        now = 1_000_000
        h.hit("k", hits=1, limit=10, duration=60_000,
              algorithm=Algorithm.LEAKY_BUCKET, now=now)
        h.hit("k", hits=1, limit=10, duration=60_000,
              algorithm=Algorithm.LEAKY_BUCKET, now=now + 5)
        exp = int(fetch_rows(h.state, [h.dir["k"]])[0, 5])
        assert exp == (now + 5) + 60_000  # not (now+5)*60_000

    def test_leaky_create_reset_time_is_now_plus_rate(self):
        """PARITY #2b: create-path ResetTime = now + rate, matching the
        existing-bucket path — not the bare rate (algorithms.go:316)."""
        h = Harness(capacity=8)
        now = 1_000_000
        _, _, _, reset = h.hit("k", hits=1, limit=10, duration=60_000,
                               algorithm=Algorithm.LEAKY_BUCKET, now=now)
        assert reset == now + 60_000 // 10

    def test_token_duration_flip_flop_takes_effect(self):
        """PARITY #2c: changing a token bucket's duration back to its
        original value must take effect (the reference silently ignores it
        because it never persists the changed duration)."""
        h = Harness(capacity=8)
        now = 1_000_000
        h.hit("k", hits=1, limit=10, duration=60_000, now=now)
        _, _, _, r2 = h.hit("k", hits=1, limit=10, duration=30_000,
                            now=now + 1)
        assert r2 == now + 30_000  # CreatedAt + new duration
        _, _, _, r3 = h.hit("k", hits=1, limit=10, duration=60_000,
                            now=now + 2)
        # back to 60s: we persist durations, so the change applies again;
        # the reference would keep the 30s expiry here
        assert r3 == now + 60_000


class TestCompactStaging:
    """The compact i32 wire format must be bit-identical to the wide i64
    format on every window it accepts (its whole correctness story), and
    must refuse windows it cannot represent."""

    @staticmethod
    def _rand_wide(rng, r, C, B, now, behaviors):
        p = np.zeros((9, B), np.int64)
        n = r.randint(1, B)
        p[0, :n] = rng.choice(C, n, replace=False)
        p[0, n:] = -1
        p[1, :n] = rng.randint(0, 6, n)
        p[2, :n] = rng.choice([1, 5, 100, 10_000, 2**30], n)
        p[3, :n] = rng.choice([500, 60_000, 2**31 - 1], n)
        p[4, :n] = rng.randint(0, 2, n)
        p[5, :n] = rng.choice(behaviors, n)
        p[8, :n] = rng.randint(0, 2, n)
        return p

    @pytest.mark.parametrize("seed", range(4))
    def test_differential_vs_wide(self, seed):
        from gubernator_tpu.ops.decide import (
            compact_window,
            decide_packed,
            decide_packed_compact,
            widen_compact_out,
        )

        r = random.Random(seed)
        rng = np.random.RandomState(seed)
        C, B, now = 256, 32, 1_700_000_000_000
        behaviors = [0, int(Behavior.RESET_REMAINING),
                     int(Behavior.NO_BATCHING)]
        wide_step = jax.jit(decide_packed)
        compact_step = jax.jit(decide_packed_compact)
        st_w, st_c = make_table(C), make_table(C)
        for i in range(12):
            wide = self._rand_wide(rng, r, C, B, now + i * 1000, behaviors)
            compact = compact_window(wide)
            assert compact is not None and compact.dtype == np.int32
            st_w, out_w = wide_step(st_w, wide, now + i * 1000)
            st_c, out_c = compact_step(st_c, compact, now + i * 1000)
            np.testing.assert_array_equal(
                np.asarray(out_w),
                widen_compact_out(out_c, now + i * 1000))
        np.testing.assert_array_equal(np.asarray(st_w), np.asarray(st_c))

    def test_scan_differential_vs_wide(self):
        from gubernator_tpu.ops.decide import (
            compact_window,
            decide_scan_packed,
            decide_scan_packed_compact,
            widen_compact_out,
        )

        r = random.Random(9)
        rng = np.random.RandomState(9)
        C, K, B, now = 256, 6, 16, 1_700_000_000_000
        wide = np.stack([
            self._rand_wide(rng, r, C, B, now, [0]) for _ in range(K)])
        compact = compact_window(wide)
        assert compact is not None and compact.shape == (K, 5, B)
        st_w, out_w = jax.jit(decide_scan_packed)(make_table(C), wide, now)
        st_c, out_c = jax.jit(decide_scan_packed_compact)(
            make_table(C), compact, now)
        np.testing.assert_array_equal(
            np.asarray(out_w), widen_compact_out(out_c, now))
        np.testing.assert_array_equal(np.asarray(st_w), np.asarray(st_c))

    def test_rejects_what_it_cannot_represent(self):
        from gubernator_tpu.ops.decide import compact_window

        base = np.zeros((9, 4), np.int64)
        base[0] = [0, 1, 2, -1]
        base[1:4] = 1
        assert compact_window(base) is not None
        too_big = base.copy()
        too_big[2, 1] = 2**31  # limit exceeds i32
        assert compact_window(too_big) is None
        negative = base.copy()
        negative[1, 0] = -1  # negative hits
        assert compact_window(negative) is None
        greg = base.copy()
        greg[5, 2] = int(Behavior.DURATION_IS_GREGORIAN)
        assert compact_window(greg) is None

    def test_reset_delta_sentinel(self):
        """RESET_REMAINING answers reset_time=0 absolute; the compact delta
        encoding must round-trip that exactly."""
        from gubernator_tpu.ops.decide import (
            compact_window,
            decide_packed,
            decide_packed_compact,
            widen_compact_out,
        )

        now = 1_700_000_000_000
        st_w, st_c = make_table(16), make_table(16)
        mk = np.zeros((9, 2), np.int64)
        mk[0] = [3, -1]
        mk[1, 0], mk[2, 0], mk[3, 0] = 2, 10, 60_000
        st_w, _ = decide_packed(st_w, mk, now)
        st_c, _ = decide_packed_compact(st_c, compact_window(mk), now)
        rr = mk.copy()
        rr[5, 0] = int(Behavior.RESET_REMAINING)
        st_w, out_w = decide_packed(st_w, rr, now + 5)
        st_c, out_c = decide_packed_compact(
            st_c, compact_window(rr), now + 5)
        out_w = np.asarray(out_w)
        assert out_w[3, 0] == 0  # absolute zero from the wide kernel
        np.testing.assert_array_equal(
            out_w, widen_compact_out(out_c, now + 5))


class TestInternedStaging:
    """The interned i32[2, B] + config-table wire format must be
    bit-identical to the wide i64 format on every window it accepts, and
    must refuse windows it cannot represent (hits >= 2^15, > 256 distinct
    (limit, duration) pairs, gregorian, values outside i32)."""

    @pytest.mark.parametrize("seed", range(4))
    def test_differential_vs_wide(self, seed):
        from gubernator_tpu.ops.decide import (
            decide_packed,
            decide_packed_interned,
            intern_window,
            widen_compact_out,
        )

        r = random.Random(seed)
        rng = np.random.RandomState(seed)
        C, B, now = 256, 32, 1_700_000_000_000
        behaviors = [0, int(Behavior.RESET_REMAINING),
                     int(Behavior.NO_BATCHING)]
        wide_step = jax.jit(decide_packed)
        int_step = jax.jit(decide_packed_interned)
        st_w, st_i = make_table(C), make_table(C)
        for i in range(12):
            wide = TestCompactStaging._rand_wide(
                rng, r, C, B, now + i * 1000, behaviors)
            interned = intern_window(wide)
            assert interned is not None
            iw, cfg = interned
            assert iw.dtype == np.int32 and iw.shape == (2, B)
            assert cfg.shape == (256, 2)
            st_w, out_w = wide_step(st_w, wide, now + i * 1000)
            st_i, out_i = int_step(st_i, iw, cfg, now + i * 1000)
            np.testing.assert_array_equal(
                np.asarray(out_w),
                widen_compact_out(out_i, now + i * 1000))
        np.testing.assert_array_equal(np.asarray(st_w), np.asarray(st_i))

    def test_scan_differential_vs_wide(self):
        from gubernator_tpu.ops.decide import (
            decide_scan_packed,
            decide_scan_packed_interned,
            intern_window,
            widen_compact_out,
        )

        r = random.Random(11)
        rng = np.random.RandomState(11)
        C, K, B, now = 256, 6, 16, 1_700_000_000_000
        wide = np.stack([
            TestCompactStaging._rand_wide(rng, r, C, B, now, [0])
            for _ in range(K)])
        interned = intern_window(wide)
        assert interned is not None
        iw, cfg = interned
        assert iw.shape == (K, 2, B)
        st_w, out_w = jax.jit(decide_scan_packed)(make_table(C), wide, now)
        st_i, out_i = jax.jit(decide_scan_packed_interned)(
            make_table(C), iw, cfg, now)
        np.testing.assert_array_equal(
            np.asarray(out_w), widen_compact_out(out_i, now))
        np.testing.assert_array_equal(np.asarray(st_w), np.asarray(st_i))

    def test_rejects_what_it_cannot_represent(self):
        from gubernator_tpu.ops.decide import intern_window

        base = np.zeros((9, 4), np.int64)
        base[0] = [0, 1, 2, -1]
        base[1:4] = 1
        assert intern_window(base) is not None
        big_hits = base.copy()
        big_hits[1, 1] = 1 << 15  # hits exceed the 15-bit lane
        assert intern_window(big_hits) is None
        neg = base.copy()
        neg[1, 0] = -1
        assert intern_window(neg) is None
        too_big = base.copy()
        too_big[2, 1] = 2**31  # limit exceeds i32
        assert intern_window(too_big) is None
        greg = base.copy()
        greg[5, 2] = int(Behavior.DURATION_IS_GREGORIAN)
        assert intern_window(greg) is None
        # exactly INTERN_MAX_CFG distinct pairs -> accepted (boundary);
        # one more -> refused. No padding lanes, so the pair count is
        # exactly the distinct-limit count.
        from gubernator_tpu.ops.decide import INTERN_MAX_CFG

        many = np.zeros((9, INTERN_MAX_CFG + 1), np.int64)
        many[0] = np.arange(INTERN_MAX_CFG + 1)
        many[1] = 1
        many[2] = np.arange(INTERN_MAX_CFG + 1) + 1  # 257 distinct limits
        many[3] = 1000
        assert intern_window(many) is None
        many[2, INTERN_MAX_CFG] = many[2, 0]  # exactly 256 distinct
        got = intern_window(many)
        assert got is not None
        iw, cfg = got
        # every config row is populated and round-trips the right pair
        assert sorted(cfg[:, 0].tolist()) == sorted(
            many[2, :INTERN_MAX_CFG].tolist())
        cfgids = (iw[1] >> 23) & 0xFF
        np.testing.assert_array_equal(cfg[cfgids, 0], many[2])
        np.testing.assert_array_equal(cfg[cfgids, 1], many[3])

    def test_hits_zero_peek_and_fresh(self):
        """hits=0 peek and the fresh flag survive the meta-word packing."""
        from gubernator_tpu.ops.decide import (
            decide_packed,
            decide_packed_interned,
            intern_window,
            widen_compact_out,
        )

        now = 1_700_000_000_000
        st_w, st_i = make_table(16), make_table(16)
        mk = np.zeros((9, 2), np.int64)
        mk[0] = [3, -1]
        mk[1, 0], mk[2, 0], mk[3, 0], mk[8, 0] = 2, 10, 60_000, 1
        iw, cfg = intern_window(mk)
        st_w, _ = decide_packed(st_w, mk, now)
        st_i, _ = decide_packed_interned(st_i, iw, cfg, now)
        peek = mk.copy()
        peek[1, 0] = 0  # hits=0: report, never deduct
        peek[8, 0] = 0
        iw2, cfg2 = intern_window(peek)
        st_w, out_w = decide_packed(st_w, peek, now + 5)
        st_i, out_i = decide_packed_interned(st_i, iw2, cfg2, now + 5)
        np.testing.assert_array_equal(
            np.asarray(out_w), widen_compact_out(out_i, now + 5))
        np.testing.assert_array_equal(np.asarray(st_w), np.asarray(st_i))

    def test_intern_cache_matches_intern_window(self):
        """InternCache must produce meta words that decode to the same
        requests as the one-shot interner (ids may differ; the decoded
        (limit, duration) must not), across windows that grow the table."""
        from gubernator_tpu.ops.decide import (
            INTERN_MAX_CFG,
            InternCache,
            decide_packed,
            decide_packed_interned,
            intern_window,
            widen_compact_out,
        )

        r = random.Random(21)
        rng = np.random.RandomState(21)
        C, B, now = 256, 32, 1_700_000_000_000
        cache = InternCache()
        wide_step = jax.jit(decide_packed)
        int_step = jax.jit(decide_packed_interned)
        st_w, st_i = make_table(C), make_table(C)
        for i in range(10):
            wide = TestCompactStaging._rand_wide(rng, r, C, B, now, [0])
            iw = cache.intern(wide)
            assert iw is not None
            st_w, out_w = wide_step(st_w, wide, now + i)
            st_i, out_i = int_step(st_i, iw, cache.cfg, now + i)
            np.testing.assert_array_equal(
                np.asarray(out_w), widen_compact_out(out_i, now + i))
        np.testing.assert_array_equal(np.asarray(st_w), np.asarray(st_i))
        assert cache.n_cfg <= INTERN_MAX_CFG

    def test_intern_cache_overflow_and_ineligible_leave_cache_intact(self):
        from gubernator_tpu.ops.decide import INTERN_MAX_CFG, InternCache

        cache = InternCache()
        base = np.zeros((9, 4), np.int64)
        base[0] = [0, 1, 2, -1]
        base[1] = 1
        base[2] = [7, 7, 7, 0]
        base[3] = 1000
        assert cache.intern(base) is not None
        n0 = cache.n_cfg
        greg = base.copy()
        greg[5, 1] = int(Behavior.DURATION_IS_GREGORIAN)
        assert cache.intern(greg) is None
        assert cache.n_cfg == n0
        # overflow: more new pairs than the table has room for
        many = np.zeros((9, INTERN_MAX_CFG + 1), np.int64)
        many[0] = np.arange(INTERN_MAX_CFG + 1)
        many[1] = 1
        many[2] = np.arange(INTERN_MAX_CFG + 1) + 100
        many[3] = 999
        assert cache.intern(many) is None
        assert cache.n_cfg == n0  # rejected atomically
        assert cache.intern(base) is not None  # still serving


class TestLeanStaging:
    """The 4-byte lean lane (i32[B] + i64[128, 4] config table, hits = 1
    implied — DESIGN.md "Next wire lever") must be bit-identical to the
    wide i64 format on every window it accepts, and must refuse windows it
    cannot represent (hits != 1, > 128 distinct configs, gregorian, values
    outside i32, capacity past 24 bits)."""

    @staticmethod
    def _rand_wide_lean(rng, r, C, B, now, behaviors):
        """TestCompactStaging._rand_wide with every live lane at hits=1
        (the lean format's defining constraint)."""
        p = TestCompactStaging._rand_wide(rng, r, C, B, now, behaviors)
        p[1, p[0] >= 0] = 1
        return p

    @pytest.mark.parametrize("seed", range(4))
    def test_differential_vs_wide(self, seed):
        from gubernator_tpu.ops.decide import (
            decide_packed,
            decide_packed_lean,
            lean_window,
            widen_compact_out,
        )

        r = random.Random(seed)
        rng = np.random.RandomState(seed)
        C, B, now = 256, 32, 1_700_000_000_000
        behaviors = [0, int(Behavior.RESET_REMAINING),
                     int(Behavior.NO_BATCHING)]
        wide_step = jax.jit(decide_packed)
        lean_step = jax.jit(decide_packed_lean)
        st_w, st_l = make_table(C), make_table(C)
        for i in range(12):
            wide = self._rand_wide_lean(rng, r, C, B, now + i * 1000,
                                        behaviors)
            got = lean_window(wide, C)
            assert got is not None
            lanes, cfg = got
            assert lanes.dtype == np.int32 and lanes.shape == (B,)
            assert cfg.shape == (128, 4)
            st_w, out_w = wide_step(st_w, wide, now + i * 1000)
            st_l, out_l = lean_step(st_l, lanes, cfg, now + i * 1000)
            np.testing.assert_array_equal(
                np.asarray(out_w),
                widen_compact_out(out_l, now + i * 1000))
        np.testing.assert_array_equal(np.asarray(st_w), np.asarray(st_l))

    def test_scan_differential_vs_wide(self):
        from gubernator_tpu.ops.decide import (
            decide_scan_packed,
            decide_scan_packed_lean,
            lean_window,
            widen_compact_out,
        )

        r = random.Random(13)
        rng = np.random.RandomState(13)
        C, K, B, now = 256, 6, 16, 1_700_000_000_000
        wide = np.stack([
            self._rand_wide_lean(rng, r, C, B, now, [0])
            for _ in range(K)])
        got = lean_window(wide, C)
        assert got is not None
        lanes, cfg = got
        assert lanes.shape == (K, B)
        st_w, out_w = jax.jit(decide_scan_packed)(make_table(C), wide, now)
        st_l, out_l = jax.jit(decide_scan_packed_lean)(
            make_table(C), lanes, cfg, now)
        np.testing.assert_array_equal(
            np.asarray(out_w), widen_compact_out(out_l, now))
        np.testing.assert_array_equal(np.asarray(st_w), np.asarray(st_l))

    def test_sign_bit_config_ids(self):
        """cfgid >= 64 sets i32 bit 31 — the lane word goes NEGATIVE on
        the wire and must still decode bit-exact (every reader masks)."""
        from gubernator_tpu.ops.decide import (
            LEAN_MAX_CFG,
            decide_packed,
            decide_packed_lean,
            lean_window,
            widen_compact_out,
        )

        now = 1_700_000_000_000
        C, B = 1 << 20, LEAN_MAX_CFG
        p = np.zeros((9, B), np.int64)
        p[0] = np.arange(B) + (C - B - 1)  # slots near the capacity edge
        p[1] = 1
        p[2] = np.arange(B) + 1  # exactly 128 distinct configs
        p[3] = 60_000
        lanes, cfg = lean_window(p, C)
        assert (lanes < 0).any()
        st_w, out_w = jax.jit(decide_packed)(make_table(C), p, now)
        st_l, out_l = jax.jit(decide_packed_lean)(
            make_table(C), lanes, cfg, now)
        np.testing.assert_array_equal(
            np.asarray(out_w), widen_compact_out(out_l, now))
        np.testing.assert_array_equal(np.asarray(st_w), np.asarray(st_l))

    def test_rejects_what_it_cannot_represent(self):
        from gubernator_tpu.ops.decide import LEAN_MAX_CFG, lean_window

        C = 1 << 20
        base = np.zeros((9, 4), np.int64)
        base[0] = [0, 1, 2, -1]
        base[1, :3] = 1
        base[2:4, :] = 1
        assert lean_window(base, C) is not None
        multi = base.copy()
        multi[1, 1] = 2  # hits != 1 cannot ride (hits is implied)
        assert lean_window(multi, C) is None
        peek = base.copy()
        peek[1, 0] = 0  # ... including hits=0 peeks
        assert lean_window(peek, C) is None
        too_big = base.copy()
        too_big[2, 1] = 2**31  # limit exceeds i32
        assert lean_window(too_big, C) is None
        greg = base.copy()
        greg[5, 2] = int(Behavior.DURATION_IS_GREGORIAN)
        assert lean_window(greg, C) is None
        # capacity gate: slots must fit 24 bits with 0xFFFFFF reserved
        assert lean_window(base, 1 << 24) is None
        assert lean_window(base, (1 << 24) - 1) is not None
        # config-count boundary: 129 distinct tuples refused, 128 accepted
        many = np.zeros((9, LEAN_MAX_CFG + 1), np.int64)
        many[0] = np.arange(LEAN_MAX_CFG + 1)
        many[1] = 1
        many[2] = np.arange(LEAN_MAX_CFG + 1) + 1
        many[3] = 1000
        assert lean_window(many, C) is None
        many[2, LEAN_MAX_CFG] = many[2, 0]
        got = lean_window(many, C)
        assert got is not None
        lanes, cfg = got
        cfgids = (lanes.astype(np.int64) >> 25) & 0x7F
        np.testing.assert_array_equal(cfg[cfgids, 0], many[2])
        np.testing.assert_array_equal(cfg[cfgids, 1], many[3])
        # algorithm/behavior fold into the config tuple, not the lane word
        ab = base.copy()
        ab[4, :3] = [0, 1, 0]
        ab[5, :3] = [0, 0, int(Behavior.RESET_REMAINING)]
        lanes, cfg = lean_window(ab, C)
        cfgids = (lanes.astype(np.int64) >> 25) & 0x7F
        np.testing.assert_array_equal(cfg[cfgids[:3], 2], ab[4, :3])
        np.testing.assert_array_equal(cfg[cfgids[:3], 3], ab[5, :3])

    def test_fresh_and_padding(self):
        """The fresh bit survives the lane word; padding lanes ride the
        0xFFFFFF sentinel and never touch the table."""
        from gubernator_tpu.ops.decide import (
            decide_packed,
            decide_packed_lean,
            lean_window,
            widen_compact_out,
        )

        now = 1_700_000_000_000
        st_w, st_l = make_table(16), make_table(16)
        mk = np.zeros((9, 4), np.int64)
        mk[0] = [3, 5, -1, -1]
        mk[1, :2] = 1
        mk[2, :2] = 10
        mk[3, :2] = 60_000
        mk[8, :2] = [1, 0]
        lanes, cfg = lean_window(mk, 16)
        assert (np.asarray(lanes[2:]) & 0xFFFFFF == 0xFFFFFF).all()
        st_w, out_w = jax.jit(decide_packed)(st_w, mk, now)
        st_l, out_l = jax.jit(decide_packed_lean)(st_l, lanes, cfg, now)
        np.testing.assert_array_equal(
            np.asarray(out_w), widen_compact_out(out_l, now))
        np.testing.assert_array_equal(np.asarray(st_w), np.asarray(st_l))

"""A scan group with the lanes' rows as the carry (ops/decide.py
_scan_carried; Engine._apply_windows_scanned aligns the lanes) against the
same group with the table as the carry, and against one launch a round:

- the three carried programs (wide, compact, lean) leave the table and the
  responses bit-identical to the table-carried scan and to K decide_packed
  calls, over seeded groups that mix both algorithms, `fresh` lanes, peeks
  (`hits` 0), requests over the limit, buckets drained to zero inside the
  group, RESET_REMAINING, a limit and a duration that change between two
  occurrences of a key, padding lanes and a lane that first lives in a
  later round;
- the engine takes the carry for the nested groups preprocess() makes and
  the table for one that is not nested, and answers as the oracle does
  either way, the ledger's lanes included.
"""

import sys

import numpy as np
import pytest

import jax

import gubernator_tpu.ops.decide  # noqa: F401  (the package re-exports the function)
from gubernator_tpu.models.engine import Engine
from gubernator_tpu.obs.ledger import DecisionLedger
from gubernator_tpu.ops.oracle import oracle_answer
from gubernator_tpu.types import Algorithm, Behavior, RateLimitReq

D = sys.modules["gubernator_tpu.ops.decide"]

C, W = 256, 32
NOW = 1_700_000_000_000
RESET = int(Behavior.RESET_REMAINING)


@jax.jit
def _single(state, packed, now):
    return D.decide_packed(state, packed, now)


def _seeded_table(rng):
    """A table that already holds buckets (some spent, some drained, some
    expired by NOW) on half its rows, through the program itself."""
    packed = np.zeros((9, C // 2), np.int64)
    packed[0] = rng.permutation(C)[:C // 2]
    packed[1] = rng.integers(0, 12, C // 2)
    packed[2] = rng.choice([3, 10, 100], C // 2)
    packed[3] = rng.choice([500, 60_000, 3_600_000], C // 2)  # 500: expired
    packed[4] = rng.integers(0, 2, C // 2)
    packed[8] = 1
    state, _ = _single(D.make_table(C), packed, NOW - 1000)
    return state


def _group(rng, depth, lean):
    """A lane-aligned stack i64[depth, 9, W]: a lane holds one slot in the
    rounds it is live and -1 in the others."""
    n = W - 6  # the last lanes are padding through the whole stack
    slots = rng.permutation(C)[:n]
    first = np.zeros(n, np.int64)  # the round a lane first lives in
    first[n - 4:] = rng.integers(1, depth, 4)  # a later round for four
    last = np.maximum(first, rng.integers(0, depth, n))
    last[:3] = depth - 1  # three keys stay to the end
    algo = rng.integers(0, 2, n)
    limit = rng.choice([1, 3, 10, 100], n)
    dur = rng.choice([60_000, 3_600_000], n)
    fresh = rng.random(n) < 0.4
    stack = np.zeros((depth, 9, W), np.int64)
    stack[:, 0, :] = -1
    for k in range(depth):
        live = np.flatnonzero((first <= k) & (k <= last))
        # a limit and a duration that change between two occurrences
        lim = np.where(rng.random(n) < 0.15, rng.choice([2, 5, 50], n), limit)
        du = np.where(rng.random(n) < 0.15, 1_800_000, dur)
        hits = np.ones(n, np.int64) if lean else rng.choice(
            [0, 1, 1, 2, 3, 7, 200], n)  # peeks, and more than any limit
        beh = np.where(rng.random(n) < 0.1, RESET, 0)
        stack[k, 0, live] = slots[live]
        stack[k, 1, live] = hits[live]
        stack[k, 2, live] = lim[live]
        stack[k, 3, live] = du[live]
        stack[k, 4, live] = algo[live]
        stack[k, 5, live] = beh[live]
        stack[k, 8, live] = fresh[live] & (first[live] == k)
    return stack


_PROGRAMS = {
    "wide": (D.decide_scan_carried, D.decide_scan_packed,
             lambda stack: (stack,), lambda out: np.asarray(out)),
    "compact": (D.decide_scan_carried_compact, D.decide_scan_packed_compact,
                lambda stack: (D.compact_window(stack),),
                lambda out: D.widen_compact_out(out, NOW)),
    "lean": (D.decide_scan_carried_lean, D.decide_scan_packed_lean,
             lambda stack: D.lean_window(stack, C),
             lambda out: D.widen_compact_out(out, NOW)),
}


@pytest.mark.parametrize("depth", [2, 3, 4, 8, 16, 32])
@pytest.mark.parametrize("staging", ["wide", "compact", "lean"])
def test_a_carried_scan_is_the_table_carried_one_bit_for_bit(staging, depth):
    carried, tabled, stage, widen = _PROGRAMS[staging]
    for seed in range(3):
        rng = np.random.default_rng(1000 * depth + seed)
        state = np.asarray(_seeded_table(rng))
        stack = _group(rng, depth, lean=staging == "lean")
        staged = stage(stack)
        assert staged is not None and staged[0] is not None
        st_c, out_c = jax.jit(carried)(state, *staged, NOW)
        st_t, out_t = jax.jit(tabled)(state, *staged, NOW)
        assert np.array_equal(np.asarray(st_c), np.asarray(st_t))
        assert np.array_equal(np.asarray(out_c), np.asarray(out_t))
        # and K launches of one window each
        st_k, rows = state, []
        for k in range(depth):
            st_k, out = _single(st_k, stack[k], NOW)
            rows.append(np.asarray(out))
        assert np.array_equal(np.asarray(st_c), np.asarray(st_k))
        assert np.array_equal(widen(out_c), np.stack(rows))
        # the group did what the docstring lists
        live = stack[:, 0, :] >= 0
        status = widen(out_c)[:, 0, :]
        assert (status[live] == 1).any() and (status[live] == 0).any()
        assert not live[:, W - 6:].any() and live[:, 0].all()
        assert not np.array_equal(np.asarray(st_c), state)


def test_a_padding_lane_gets_its_row_back():
    """What the carry rests on: decide_rows hands a lane whose slot is -1
    its row as it was (no field rewritten, the hit counter not bumped)."""
    rng = np.random.default_rng(3)
    rows = D.load_rows(_seeded_table(rng), np.arange(W, dtype=np.int32))
    stack = _group(rng, 2, lean=False)
    stack[0, 0, ::2] = -1
    reqs = D._wide_reqs(stack[0])
    new_rows, resp = jax.jit(D.decide_rows)(rows, reqs, NOW)
    pad = stack[0, 0] < 0
    assert pad.any() and not pad.all()
    assert np.array_equal(np.asarray(new_rows)[pad], np.asarray(rows)[pad])
    assert not np.array_equal(np.asarray(new_rows)[~pad],
                              np.asarray(rows)[~pad])
    assert not np.asarray(D._wide_response(resp))[:, pad].any()


# ------------------------------------------------------------- the engine


def _req(key, hits=1, limit=10, duration=60_000, algorithm=0, behavior=0):
    return RateLimitReq(name="carry", unique_key=key, hits=hits, limit=limit,
                        duration=duration, algorithm=Algorithm(algorithm),
                        behavior=behavior)


def _repeating_call(rng, n, n_keys, skew=1.3):
    """A call in which a few keys stand many times (Zipf over `n_keys`)."""
    ranks = np.minimum(rng.zipf(skew, n), n_keys) - 1
    return [_req(f"k{r}", hits=int(rng.choice([0, 1, 1, 2, 3])),
                 limit=int([3, 10, 100][r % 3]), algorithm=int(r % 2),
                 behavior=RESET if rng.random() < 0.03 else 0)
            for r in ranks]


def _spy_scans(eng):
    """(launched shape, carried) of every scan dispatch, in order: a stack
    that holds its live lanes alone launches `width` wide."""
    seen, real = [], eng._dispatch_scan_staged
    eng._dispatch_scan_staged = lambda stacked, now_ms, carried=False, \
        live=None, width=None: (
        seen.append(((len(stacked), 9, width or stacked.shape[2]), carried)),
        real(stacked, now_ms, carried, live, width))[1]
    return seen


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_repeated_keys_ride_the_carry_and_equal_the_oracle(seed):
    rng = np.random.default_rng(seed)
    eng = Engine(capacity=1024, min_width=16, max_width=128)
    led = DecisionLedger(enabled=True)
    eng.ledger = led
    seen = _spy_scans(eng)
    table = {}
    for call in range(5):
        now = NOW + 700 * call
        reqs = _repeating_call(rng, 120, 40)
        got = eng.get_rate_limits(reqs, now_ms=now)
        want = [oracle_answer(table, r, now) for r in reqs]
        assert got == want
        # the ledger parked every lane once, under its own slot
        with led._pending_lock:
            parked, led._pending[:] = list(led._pending), []
        lanes = sorted(
            (int(sh[0, j]), int(sh[1, j]), int(resp[0, j]), int(resp[1, j]),
             int(resp[3, j]))
            for sh, resp, _auth in parked for j in range(sh.shape[1]))
        slots = eng.peek_slots([r.hash_key() for r in reqs]).tolist()
        assert lanes == sorted(
            (s, r.hits, a.status, a.limit, a.reset_time)
            for s, r, a in zip(slots, reqs, got))
    st = eng.stats
    assert seen and all(carried for _shape, carried in seen)
    assert st.scan_rounds_carried == st.scan_rounds > 2 * st.scan_dispatches
    assert st.as_dict()["scan_rounds_carried"] == st.scan_rounds_carried
    # a key that stands more than 32 times needs a second group
    assert max(shape[0] for shape, _ in seen) == Engine._MAX_SCAN


def test_a_group_that_is_not_nested_rides_the_table_and_still_matches():
    """Round 0 of a call wider than max_width is cut into chunks, so the
    tail's first window is a remainder and a later round's key lives in a
    head chunk: no lane for it, the group keeps the table-carried
    program; the next call's tail is nested and rides the carry."""
    eng = Engine(capacity=1024, min_width=8, max_width=32)
    seen = _spy_scans(eng)
    table = {}
    # 36 distinct keys (chunks of 32 + 4), then keys of the FIRST chunk again
    reqs = [_req(f"n{i}", limit=3) for i in range(36)] \
        + [_req(f"n{i}", limit=3, hits=2) for i in (0, 1, 2)] \
        + [_req("n0", limit=3)] * 3
    got = eng.get_rate_limits(reqs, now_ms=NOW)
    assert got == [oracle_answer(table, r, NOW) for r in reqs]
    assert seen == [((8, 9, 8), False)]  # [4-key remainder, 3, 1, 1, 1]
    st = eng.stats
    assert st.scan_rounds == 5 and st.scan_rounds_carried == 0
    nested = [_req("n0", limit=3), _req("n1", limit=3)] * 3
    got = eng.get_rate_limits(nested, now_ms=NOW + 5)
    assert got == [oracle_answer(table, r, NOW + 5) for r in nested]
    assert seen[1:] == [((2, 9, 8), True)]
    assert st.scan_rounds - st.scan_rounds_carried == 5


def test_on_a_one_width_ladder_the_rounds_share_the_group_programs():
    """One scan program a shape: `max_width`-wide scans are the group
    launches' (the table as the carry), so an engine whose ladder is one
    width takes those for a repeated key's rounds, as it always did."""
    rng = np.random.default_rng(9)
    eng = Engine(capacity=1024, min_width=64, max_width=64)
    seen = _spy_scans(eng)
    table = {}
    reqs = _repeating_call(rng, 60, 20)
    assert eng.get_rate_limits(reqs, now_ms=NOW) == \
        [oracle_answer(table, r, NOW) for r in reqs]
    assert seen and not any(carried for _shape, carried in seen)
    assert eng.stats.scan_rounds > 0 == eng.stats.scan_rounds_carried


def test_lane_alignment():
    a, b, c, d = (("i", _req(k), 0, 0) for k in "abcd")
    keys = lambda wk: [it[1].hash_key() for it in wk]  # noqa: E731
    group = [[a, b, c, d], [b, d], [d]]
    windows, window_keys = Engine._lane_aligned(group, [keys(w) for w in group])
    assert windows == [[d, b, a, c], [d, b], [d]]  # ties keep their order
    assert window_keys == [keys(w) for w in windows]
    for bad in ([[a, b], [b, c]],   # c has no lane
                [[a, b], [a], [b]]):  # b's lane is no prefix of round 2
        assert Engine._lane_aligned(bad, [keys(w) for w in bad]) is None
    # a key may sit a round out where the prefixes allow it: its lane is
    # padding there and the program hands its row on
    windows, _ = Engine._lane_aligned(
        [[a, b], [b], [a, b]], [keys(w) for w in ([a, b], [b], [a, b])])
    assert windows == [[b, a], [b], [b, a]]

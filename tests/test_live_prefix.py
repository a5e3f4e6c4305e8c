"""The host walks a launch's live lanes, not its launched width.

- the wire-format converters (ops/decide.py lean_window, compact_window,
  widen_compact_out, pad_window) over a window's or a stack's live prefix
  give the arrays they give over the whole buffer, and refuse where they
  refuse;
- Engine's funnels carry the prefix from every caller: a scan group's
  stack is as wide as its widest window and launches at the ladder's
  width, `stats.staged_lanes` counts what was walked, a stack no narrow
  format takes still ships wide, and no program changes;
- every entry answers as the oracle does whichever window of a group is
  the widest: a prefix taken from the wrong window drops lanes silently.
"""

import sys

import numpy as np
import pytest

import gubernator_tpu.ops.decide  # noqa: F401  (the package re-exports the function)
from gubernator_tpu.models.engine import Engine
from gubernator_tpu.ops.oracle import oracle_answer
from gubernator_tpu.types import Algorithm, Behavior, RateLimitReq
from test_mesh_deployment import SLOW, _cols, _outs  # the wire's columns

D = sys.modules["gubernator_tpu.ops.decide"]

NOW = 1_700_000_000_000
C = 10_000_001  # the benchmark's table: slots fit the lean lane word
GREG = int(Behavior.DURATION_IS_GREGORIAN)


# ------------------------------------------------------------ converters

def _stack(rng, k, w, live, lean=False):
    """A wide i64[k, 9, w] stack ([9, w] for k == 1) whose lanes beyond
    `live` are padding; inside the prefix some lanes sit rounds out."""
    s = np.zeros((k, 9, w), np.int64)
    s[:, 0, :] = -1
    held = rng.random((k, live)) < 0.8
    s[:, 0, :live] = np.where(held, rng.integers(0, 1 << 23, (k, live)), -1)
    s[:, 1, :live] = 1 if lean else rng.integers(0, 9, (k, live))
    s[:, 2, :live] = rng.choice([1, 10, 1000, (1 << 31) - 1], (k, live))
    s[:, 3, :live] = rng.choice([500, 60_000, 3_600_000], (k, live))
    s[:, 4, :live] = rng.integers(0, 2, (k, live))
    s[:, 5, :live] = rng.choice([0, 0, 8, 32], (k, live))
    s[:, 8, :live] = rng.integers(0, 2, (k, live))
    s[:, 1:, :live] *= held[:, None, :]  # a lane that sits out is padding
    return s[0] if k == 1 else s


@pytest.mark.parametrize("live", [0, 1, 90, None], ids=lambda v: f"live{v}")
@pytest.mark.parametrize("w", [64, 2048, 8192])
@pytest.mark.parametrize("k", [1, 2, 32])
def test_prefix_forms_equal_the_whole_buffer(k, w, live):
    live = w if live is None else min(live, w)
    rng = np.random.default_rng(1000 * k + w + live)
    stack = _stack(rng, k, w, live)
    prefix = stack[..., :live]
    whole = D.compact_window(stack)
    assert whole is not None and whole.shape[-1] == w
    got = D.compact_window(prefix, w)
    assert got.dtype == whole.dtype and np.array_equal(got, whole)
    # hits != 1 on some lane of any but the shortest prefix: both refuse
    assert (D.lean_window(prefix, C, w) is None) \
        == (D.lean_window(stack, C) is None)
    assert live < 64 or D.lean_window(prefix, C, w) is None
    assert np.array_equal(D.pad_window(prefix, w), stack)

    lean = _stack(rng, k, w, live, lean=True)
    lanes, cfg = D.lean_window(lean, C)
    got_lanes, got_cfg = D.lean_window(lean[..., :live], C, w)
    assert got_lanes.dtype == lanes.dtype
    assert np.array_equal(got_lanes, lanes) and np.array_equal(got_cfg, cfg)

    # the answers: status, limit, remaining, reset delta (-1: absolute 0)
    out = rng.integers(0, 1 << 20, whole.shape[:-2] + (4, w)).astype(np.int32)
    out[..., 3, :] = np.where(rng.random(out[..., 3, :].shape) < 0.3, -1,
                              out[..., 3, :])
    out[..., 3, 0] = -1
    want = D.widen_compact_out(out, NOW)
    got = D.widen_compact_out(out, NOW, live)
    assert got.dtype == np.int64 and np.array_equal(got, want[..., :live])
    assert not live or (got[..., 3, :] == 0).any()  # the sentinel decoded


def _refusal(name):
    """(stack, live): one ineligible lane inside the prefix of an
    otherwise lean stack, and which of (compact, lean) must refuse it."""
    rng = np.random.default_rng(7)
    k, w, live = 2, 2048, 90
    s = _stack(rng, k, w, live, lean=True)
    s[:, 0, :live] = np.maximum(s[:, 0, :live], 0)  # every lane held
    s[:, 1, :live] = 1
    if name == "gregorian":
        s[1, 5, 17] |= GREG
        return s, live, (True, True)
    if name == "limit_2_31":
        s[0, 2, live - 1] = 1 << 31
        return s, live, (True, True)
    if name == "negative_duration":
        s[1, 3, 0] = -5
        return s, live, (True, True)
    if name == "hits_2":
        s[0, 1, 3] = 2
        return s, live, (False, True)
    if name == "cfg_tuples":
        s[:, 2, :live] = np.arange(k * live).reshape(k, live) + 1
        return s, live, (False, True)  # 180 > LEAN_MAX_CFG tuples
    assert name == "slot_2_24"
    s[1, 0, 40] = 1 << 24
    return s, live, (False, True)


@pytest.mark.parametrize("name", ["gregorian", "limit_2_31",
                                  "negative_duration", "hits_2",
                                  "cfg_tuples", "slot_2_24"])
def test_prefix_forms_refuse_where_the_whole_buffer_does(name):
    s, live, (compact_refuses, lean_refuses) = _refusal(name)
    w = s.shape[-1]
    assert D.LEAN_MAX_CFG < 180
    for form in (D.compact_window(s), D.compact_window(s[..., :live], w)):
        assert (form is None) == compact_refuses
    for form in (D.lean_window(s, C), D.lean_window(s[..., :live], C, w)):
        assert (form is None) == lean_refuses
    if not compact_refuses:
        assert np.array_equal(D.compact_window(s[..., :live], w),
                              D.compact_window(s))


# ------------------------------------------------------- engine, counters

def _req(key, hits=1, limit=100, duration=60_000, algorithm=0, behavior=0):
    return RateLimitReq(name="lp", unique_key=key, hits=hits, limit=limit,
                        duration=duration, algorithm=Algorithm(algorithm),
                        behavior=behavior)


def _spy(eng):
    """What the scan funnel was handed: (stack shape, launched width,
    carried, kernel names dispatched since) per dispatch."""
    seen, real = [], eng._dispatch_scan_staged

    def spy(stacked, now_ms, carried=False, live=None, width=None):
        before = D.kernel_telemetry.counts()
        handle = real(stacked, now_ms, carried, live, width)
        after = D.kernel_telemetry.counts()
        kernels = {k for (k, _w), n in after.items()
                   if n != before.get((k, _w), 0)}
        seen.append((stacked.shape, width, carried, kernels))
        return handle

    eng._dispatch_scan_staged = spy
    return seen


# taken from the parent commit (Engine(capacity=1024, min_width=16,
# max_width=128).kernel_fingerprints() on the CPU): no program changed
_PARENT_FINGERPRINTS = {
    "carry_wide@16": "f4fdb7f6f86d36a4",
    "packed_wide@16": "0c762a3ed6a538b5",
    "scan_wide@16": "874d0ef69a0bab03",
}


def test_kernel_fingerprints_are_the_parents():
    eng = Engine(capacity=1024, min_width=16, max_width=128)
    assert eng.kernel_fingerprints() == _PARENT_FINGERPRINTS


@pytest.mark.parametrize("gregorian", [False, True],
                         ids=["compact", "gregorian_ships_wide"])
def test_scan_group_is_staged_by_its_live_prefix(gregorian):
    """A key repeated 40 times among 90 distinct keys at the hot cell's
    ladder: two scan groups (32 rounds, then 8), each staged 90 and 1
    lanes wide and launched 2048 wide."""
    from gubernator_tpu.utils import GREGORIAN_HOURS

    lo, hi = 2048, 8192
    eng = Engine(capacity=4096, min_width=lo, max_width=hi)
    seen = _spy(eng)
    behavior = GREG if gregorian else 0
    # hits 2: the lean wire refuses, as it does in hot10m.repeats1000
    reqs = [_req(f"k{i}", hits=2, algorithm=i % 2) for i in range(89)]
    hot = _req("hot", hits=2, limit=50, behavior=behavior,
               duration=GREGORIAN_HOURS if gregorian else 60_000)
    calls = reqs + [hot] * 40
    calls = [calls[j] for j in np.random.default_rng(3).permutation(129)]
    table = {}
    for call in range(2):  # the second call finds the rows it left
        now = NOW + 700 * call
        before = eng.stats.as_dict()
        got = eng._slow_window(calls, now)
        assert got == [oracle_answer(table, r, now) for r in calls]
        d = {k: v - before[k] for k, v in eng.stats.as_dict().items()}
        assert d["scan_dispatches"] == 2 and d["scan_rounds"] == 40
        assert d["scan_rounds_carried"] == 40
        assert d["scan_lanes"] == (32 + 8) * lo
        assert d["scan_lanes_live"] == 90 + 39
        if gregorian:  # the first group ships its wide format whole
            assert d["staged_lanes"] == 32 * lo + 8 * lo
        else:
            assert d["staged_lanes"] == 32 * 90 + 8 * 1
    shapes = [(shape, width, carried) for shape, width, carried, _k in seen]
    # no i64[K, 9, W] on the host: the stack is its live lanes wide
    assert shapes == [((32, 9, 90), lo, True), ((8, 9, 1), lo, True)] * 2
    kernels = [k for *_rest, k in seen]
    want = {"carry_wide"} if gregorian else {"carry_compact"}
    assert kernels == [want] * 4
    # the table rows are the oracle's: every key's next answer agrees
    now = NOW + 5000
    probe = [_req(f"k{i}", hits=0, algorithm=i % 2) for i in range(89)]
    assert eng.get_rate_limits(probe, now_ms=now) == [
        oracle_answer(table, r, now) for r in probe]


def test_noop_and_warm_launches_ship_all_padding():
    """`live` 0: the shipped array is all padding, nothing is decided and
    nothing is walked."""
    eng = Engine(capacity=256, min_width=8, max_width=8)
    before = eng.stats.staged_lanes
    handle = eng.launch_noop()
    eng.collect_noop(handle)
    out, _compact_now, live = handle
    assert out.shape == (4, 8) and live == 0
    assert eng.stats.staged_lanes == before
    got = eng.get_rate_limits([_req("a", hits=3, limit=5)], now_ms=NOW)[0]
    assert (got.status, got.remaining) == (0, 2)


# ------------------------------------------------- caller by caller guard

def _as_rows(resps):
    return [(r.status, r.limit, r.remaining, r.reset_time) for r in resps]


def _drive(eng, entry, windows, now):
    """`windows` (request lists) through one entry point: their answers
    as (status, limit, remaining, reset_time) rows a window."""
    if entry == "fast":
        return [_as_rows(eng.get_rate_limits(wk, now_ms=now))
                for wk in windows]
    if entry == "slow":
        return [_as_rows(eng._slow_window(wk, now)) for wk in windows]
    if entry == "windows":
        handle = eng.launch_windows(windows, now_ms=now, staging={})
        assert handle is not None
        return [_as_rows(wk) for wk in eng.collect_windows(handle)]
    if entry == "columnar":
        got = []
        for wk in windows:
            outs = _outs(len(wk))
            handle = eng.submit_columnar(*_cols(wk), SLOW, now_ms=now)
            assert handle is not None
            assert not len(eng.complete_columnar(handle, *outs))
            got.append(list(zip(*(o.tolist() for o in outs))))
        return got
    assert entry == "columnar_windows"
    handle = eng.launch_columnar_windows(
        [_cols(wk) for wk in windows], SLOW, now_ms=now, staging={})
    assert handle is not None and len(handle[0]) == len(windows)
    outs = [_outs(len(wk)) for wk in windows]
    assert not any(len(left) for left in
                   eng.collect_columnar_windows(handle, outs))
    return [list(zip(*(o.tolist() for o in out))) for out in outs]


@pytest.mark.parametrize("widest", ["first", "last", "middle"])
@pytest.mark.parametrize("entry", ["fast", "slow", "windows", "columnar",
                                   "columnar_windows"])
def test_every_entry_answers_as_the_oracle(entry, widest):
    eng = Engine(capacity=4096, min_width=8, max_width=64)
    if entry != "slow" and not eng.supports_columnar():
        pytest.skip("native prep unavailable")
    sizes = {"first": [61, 3, 17], "last": [3, 17, 61],
             "middle": [17, 61, 3]}[widest]
    rng = np.random.default_rng(sizes[0])
    table = {}
    for call in range(3):  # later calls find the rows the first left
        now = NOW + 900 * call
        windows = [[_req(f"w{n}k{int(j)}", hits=int(rng.integers(0, 4)),
                         limit=int(rng.choice([3, 10, 100])),
                         algorithm=int(j) % 2)
                    for j in rng.permutation(size)]
                   for n, size in enumerate(sizes)]
        if entry == "slow":
            # repeats in a call: its rounds scan, the widest round first,
            # last or in the middle of what the call lists
            windows = [[r for wk in windows for r in wk]
                       + [windows[1][0]] * 5 + [windows[0][0]] * 2]
        before = eng.stats.as_dict()
        got = _drive(eng, entry, windows, now)
        want = [_as_rows([oracle_answer(table, r, now) for r in wk])
                for wk in windows]
        assert got == want
        d = {k: v - before[k] for k, v in eng.stats.as_dict().items()}
        assert d["requests"] == sum(len(wk) for wk in windows)
        if entry in ("windows", "columnar_windows"):
            # one scan, four deep, walked by its widest window
            assert d["scan_dispatches"] == 1 and d["scan_lanes"] == 4 * 64
            assert d["staged_lanes"] == 4 * 61
        elif entry != "slow":
            assert d["staged_lanes"] == sum(sizes)

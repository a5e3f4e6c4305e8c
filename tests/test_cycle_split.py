"""The cycle between two launches, accounted for: what `dispatch` and
`readback` are made of (obs/profile.py SUB_PHASES, stamped inside the
engines' launch and fetch funnels), the link's two counters, the engine
lock's holds, and the spans the once dark paths write into a capture
(the slow window's rounds, the combiner's two threads, the pull loop's
`pull` and `leftover.*`), on `Engine` and on `ShardedEngine` over four
virtual devices.
"""

import os
import sys
import threading

import pytest

from gubernator_tpu.models.engine import Engine
from gubernator_tpu.ops.decide import COMPACT_ROWS, LEAN_MAX_CFG
from gubernator_tpu.parallel import ShardedEngine
from gubernator_tpu.types import Behavior, RateLimitReq

from test_hot_deployment import LADDER, _Node, _residents, _traffic
from test_mesh_deployment import SLOW, _calls, _cols, _outs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:  # appended: nothing of tests/ is shadowed
    sys.path.append(BENCH)

import span_tree  # noqa: E402

NOW = 1_700_000_000_000
LOW, HIGH = 64, 256  # the ladder
N = 50  # requests a window: one 64-lane launch


def _reqs(tag, n=N, hits=1, behavior=0):
    return [RateLimitReq(name="cy", unique_key=f"{tag}:{i}", hits=hits,
                         limit=50, duration=60_000, behavior=behavior)
            for i in range(n)]


def _engine(kind):
    if kind == "engine":
        eng = Engine(capacity=4096, min_width=LOW, max_width=HIGH)
    else:
        eng = ShardedEngine(n_shards=4, capacity_per_shard=2048,
                            min_width=LOW, max_width=HIGH)
    if not eng.supports_columnar():
        pytest.skip("native prep unavailable")
    return eng


# ---- one entry point of the engine at a time


def _fast(eng):
    assert len(eng.get_rate_limits(_reqs("f"), now_ms=NOW)) == N


def _slow(eng):
    # three occurrences a key: the first rides the fast window, the other
    # two the python pipeline's rounds
    assert len(eng.get_rate_limits(_reqs("s", 20) * 3, now_ms=NOW)) == 60


def _submit_complete(eng):
    handle = eng.submit_columnar(*_cols(_reqs("c")), SLOW, now_ms=NOW)
    assert handle is not None
    assert len(eng.complete_columnar(handle, *_outs(N))) == 0


def _object_windows(eng):
    handle = eng.launch_windows([_reqs("o0"), _reqs("o1")], now_ms=NOW)
    assert handle is not None
    assert [len(r) for r in eng.collect_windows(handle)] == [N, N]


def _columnar_windows(eng):
    wins = [_cols(_reqs("w0")), _cols(_reqs("w1"))]
    handle = eng.launch_columnar_windows(wins, SLOW, now_ms=NOW)
    assert handle is not None and handle[1] is None
    left = eng.collect_columnar_windows(handle, [_outs(N), _outs(N)])
    assert [len(x) for x in left] == [0, 0]


ENTRIES = {
    "fast_window": _fast,
    "slow_window": _slow,
    "submit_columnar": _submit_complete,
    "launch_windows": _object_windows,
    "launch_columnar_windows": _columnar_windows,
}


def _phases(eng):
    return eng.profiler.endpoint_body()["phases"]


@pytest.mark.parametrize("site", sorted(ENTRIES))
@pytest.mark.parametrize("kind", ["engine", "mesh"])
@pytest.mark.parametrize("capturing", [False, True],
                         ids=["no_capture", "capture"])
def test_the_sub_phases_lie_inside_their_phase(capturing, kind, site):
    """Every launch is one `stage` and one `launch` inside one `dispatch`,
    by construction, whichever entry point the window came through. While
    a capture runs every fetch is one `device_wait` and one `fetch` inside
    one `readback`; while none does the copy waits for the device by
    itself, as it always did, and neither is observed."""
    eng = _engine(kind)
    # the flag Profiler._jax_trace holds up around a capture (annotations
    # entered outside a real one record nothing)
    eng.profiler._capturing = capturing
    ENTRIES[site](eng)
    ph = _phases(eng)
    launches = ph["launch"]["n"]
    assert launches >= 1
    assert ph["stage"]["n"] == ph["dispatch"]["n"] == launches
    assert 0 < ph["stage"]["total_ns"] + ph["launch"]["total_ns"] \
        <= ph["dispatch"]["total_ns"]
    assert ph["readback"]["n"] == launches
    assert ph["device_wait"]["n"] == ph["fetch"]["n"] \
        == (launches if capturing else 0)
    assert ph["device_wait"]["total_ns"] + ph["fetch"]["total_ns"] \
        <= ph["readback"]["total_ns"]
    if capturing:
        assert ph["fetch"]["total_ns"] > 0
    assert "alloc" not in ph  # a span of the capture, no phase
    # the parts stand outside the decomposition: its shares are the six
    # phases' as before
    assert set(eng.profiler.decomposition()) == {
        "queue_wait", "lock_wait", "prep", "dispatch", "readback", "demux"}


@pytest.mark.parametrize("site", sorted(ENTRIES))
@pytest.mark.parametrize("kind", ["engine", "mesh"])
def test_a_hold_of_the_engine_lock_is_observed_once(kind, site):
    eng = _engine(kind)
    ENTRIES[site](eng)
    body = eng.profiler.endpoint_body()
    ph, waits, holds = (body["phases"], body["lock_sites"],
                        body["lock_hold_sites"])
    # one wait and one hold an acquisition, under the site's own name
    assert set(holds) == set(waits) and site in holds
    for name in holds:
        assert holds[name]["n"] == waits[name]["n"] >= 1
    assert ph["lock_hold"]["n"] == ph["lock_wait"]["n"]
    assert ph["lock_hold"]["total_ns"] == \
        sum(h["total_ns"] for h in holds.values())
    if site != "slow_window":
        # prep and dispatch run under the lock (preprocess(), the slow
        # window's first `prep`, runs before it)
        assert ph["lock_hold"]["total_ns"] >= \
            ph["prep"]["total_ns"] + ph["dispatch"]["total_ns"]
    if site == "fast_window":  # and so do its readback and demux
        assert ph["lock_hold"]["total_ns"] >= sum(
            ph[p]["total_ns"]
            for p in ("prep", "dispatch", "readback", "demux"))


# ---- the link's counters, against what the shapes give


def _link(eng):
    d = eng.stats.as_dict() if hasattr(eng.stats, "as_dict") else eng.stats
    return (d["staged_bytes"], d["fetched_bytes"])


def _gained(eng, serve):
    before = _link(eng)
    serve()
    return tuple(a - b for a, b in zip(_link(eng), before))


LEAN_CFG = LEAN_MAX_CFG * 4 * 8  # the i64[LEAN_MAX_CFG, 4] config table
COMPACT_BACK = 4 * 4  # a compact answer: i32[4] a lane


def test_the_link_counters_are_what_the_shapes_give():
    eng = _engine("engine")
    # lean: hits == 1 and one config, an i32 a lane and the config table
    assert _gained(eng, lambda: eng.get_rate_limits(
        _reqs("lean"), now_ms=NOW)) == \
        (4 * LOW + LEAN_CFG, COMPACT_BACK * LOW)
    # compact: hits == 2 is past the lean wire, i32[COMPACT_ROWS] a lane
    assert _gained(eng, lambda: eng.get_rate_limits(
        _reqs("compact", hits=2), now_ms=NOW)) == \
        (4 * COMPACT_ROWS * LOW, COMPACT_BACK * LOW)
    # wide: a gregorian duration rides i64[9] a lane up, i64[4] back
    greg = [RateLimitReq(name="cy", unique_key=f"greg:{i}", hits=1, limit=50,
                         duration=4,  # GregorianHours... any valid unit
                         behavior=int(Behavior.DURATION_IS_GREGORIAN))
            for i in range(N)]
    assert _gained(eng, lambda: eng.get_rate_limits(greg, now_ms=NOW)) == \
        (8 * 9 * LOW, 8 * 4 * LOW)
    # a carried scan group: one key five times is one lean window and a
    # row-carried stack of four rounds, LOW lanes wide
    before = eng.stats.scan_rounds_carried
    hot = _reqs("hot", 1) * 5
    assert _gained(eng, lambda: eng.get_rate_limits(hot, now_ms=NOW)) == \
        (4 * LOW + LEAN_CFG + 4 * 4 * LOW + LEAN_CFG,
         COMPACT_BACK * LOW + COMPACT_BACK * 4 * LOW)
    assert eng.stats.scan_rounds_carried - before == 4
    assert eng.stats.scan_lanes == 4 * LOW  # the scan path's own, as before


def test_the_mesh_counts_every_chips_lanes():
    eng = _engine("mesh")
    staged, fetched = _gained(
        eng, lambda: eng.get_rate_limits(_reqs("m"), now_ms=NOW))
    # 50 keys over four shards: each shard's fullest fits the bottom width
    lanes = 4 * LOW
    assert staged == 4 * lanes + LEAN_CFG  # lean: lanes i32[1, 4, LOW]
    assert fetched == COMPACT_BACK * lanes
    assert eng.stats["lean_windows"] == 1


def test_concurrent_completers_keep_the_counters_exact():
    """fetched_bytes is added where over_limit is, under the engine lock:
    two threads collecting at once lose no update."""
    eng = _engine("engine")
    eng.get_rate_limits(_reqs("warm"), now_ms=NOW)
    base = _link(eng)[1]
    rounds, threads = 40, 4
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        def work(t):
            for k in range(rounds):
                h = eng.submit_columnar(*_cols(_reqs(f"t{t}.{k}")), SLOW,
                                        now_ms=NOW)
                eng.complete_columnar(h, *_outs(N))

        ts = [threading.Thread(target=work, args=(t,))
              for t in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert _link(eng)[1] - base == rounds * threads * COMPACT_BACK * LOW


# ---- the capture: the once dark paths


def test_a_capture_of_a_repeated_key_call_holds_every_thread(
        monkeypatch, tmp_path):
    """A call that repeats keys, through a served Instance, while a
    capture runs: the combiner's thread (which runs the slow window's
    rounds) writes the whole chain with the funnels' spans nested in it,
    both combiner threads write `combiner.wait`, and the pull worker's
    `pull` holds `leftover`, which holds its three children."""
    tr = _traffic(19)
    items, _ = _residents(tr)
    calls = _calls(tr)
    node = _Node(monkeypatch, LADDER, items)
    prof = node.instance.profiler
    prof.capture_min_interval_s = 0.0
    out = {}
    try:
        node.pull(calls[:1], 0)  # warm
        capture = threading.Thread(
            target=lambda: out.update(prof.capture(str(tmp_path),
                                                   seconds=0.8)))
        capture.start()
        k = 1
        while capture.is_alive() and k < 200:
            node.pull([calls[k % len(calls)]], k)
            k += 1
        capture.join(timeout=60)
        assert not capture.is_alive()
    finally:
        node.close()
    assert out.get("ok") is True and out["mode"] == "jax_trace", out
    trees = span_tree.forest(span_tree.load(out["path"]))

    def names(roots):
        return {n.name for n in span_tree.walk(roots)}

    # the combiner's launching thread: forming, then the engine's chain
    (former,) = span_tree.threads_with(trees, "combiner.form")
    assert {"combiner.wait", "combiner.form", "alloc", "lock_wait", "prep",
            "dispatch", "stage", "launch", "readback", "device_wait",
            "fetch", "demux"} <= names(trees[former])
    # both of its threads block under the same name
    waiters = span_tree.threads_with(trees, "combiner.wait")
    assert len(waiters) == 2 and former in waiters
    # the funnels' spans hang from the phase they are part of
    for child, parent in (("stage", "dispatch"), ("launch", "dispatch"),
                          ("device_wait", "readback"),
                          ("fetch", "readback")):
        for node_ in span_tree.named(trees, parent):
            if node_.thread == former:
                assert child in {c.name for c in node_.children}, parent
    # the pull worker: pull > leftover > build, serve, fill
    (worker,) = span_tree.threads_with(trees, "pull")
    pulls = [n for n in trees[worker] if n.name == "pull"]
    assert pulls
    whole = [n for n in pulls
             if any(c.name == "leftover" for c in n.children)]
    assert whole, "no pull with a leftover span inside the capture"
    for pull in whole:
        (left,) = [c for c in pull.children if c.name == "leftover"]
        assert [c.name for c in left.children] == [
            "leftover.build", "leftover.serve", "leftover.fill"]
        assert 0 <= pull.self_time <= pull.duration
    assert span_tree.self_time_mean_ms(trees, "pull") > 0

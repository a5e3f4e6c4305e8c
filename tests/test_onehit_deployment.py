"""The one-hit deployment (benchmarks/configs/node-1chip-10m-onehit.json: a
gateway that counts one hit a request against a handful of plans) at a
small table: the benchmark's own seeded traffic for its cell
(benchmarks/workloads/onehit10m.batch1000.json) after the benchmark's
restore, `Engine` behind the native directory, fed through the entries the
served path uses, every answer held field for field to
gubernator_tpu/ops/oracle.py replayed request by request.

(a) every launch of the deployment's traffic is a `*_lean` program
    (`kernel_telemetry`): a lone window (`submit_`/`complete_columnar`,
    `get_rate_limits`), a group of K windows (`launch_`/
    `collect_columnar_windows`, the combiner's `launch_windows`) and, for a
    gateway that repeats a key in a call, a row-carried scan;
(b) the same traffic under GUBER_STAGING=wide answers the same, bit for
    bit;
(c) one lane with `hits` 2, a 129th tuple, a limit of 2^31, a gregorian
    lane and a table past the lane's 24 bits each take the launch off the
    lane, the answers stay exact, and `engine.stats.lean_refused_*` names
    the reason;
(d) a launch whose table holds more than 64 tuples, so that config ids
    with bit 31 set (negative lane words) decode;
(e) the decision ledger and `rows_for_keys` read the same rows after lean
    launches as after wide ones.
"""

import copy
import json
import os
import sys

import numpy as np
import pytest

import gubernator_tpu.ops.decide  # noqa: F401  (the package re-exports the function)
from gubernator_tpu.models import engine as engine_mod
from gubernator_tpu.models.engine import Engine
from gubernator_tpu.native import NativeKeyDirectory
from gubernator_tpu.obs.ledger import DecisionLedger
from gubernator_tpu.ops.oracle import Row, oracle_answer
from gubernator_tpu.store import BucketSnapshot
from gubernator_tpu.types import Algorithm, Behavior, RateLimitReq
from gubernator_tpu.utils import GREGORIAN_HOURS

from test_mesh_deployment import SLOW, _calls, _cols, _outs

D = sys.modules["gubernator_tpu.ops.decide"]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:  # appended: nothing of tests/ is shadowed
    sys.path.append(BENCH)

import keymodel  # noqa: E402
from traffic import Traffic  # noqa: E402

CONFIG = "node-1chip-10m-onehit"
CELL = "onehit10m.batch1000"
NOW = 1_700_000_000_000
RESIDENTS = 3000
ITEMS = 60  # requests a call: one window of the 64 launched
CALLS = 12  # a client's pool; two clients are served
WIDTH = 64  # a one-width ladder, as the configuration's (8192 there)
GROUP = 3  # calls a pull hands the engine as one scan group
GREG = int(Behavior.DURATION_IS_GREGORIAN)
PARAMS = [(algos, seed) for algos in ((0,), (1,), (0, 1))
          for seed in (7, 2**31 + 33)]
IDS = [f"{'+'.join('token' if a == 0 else 'leaky' for a in algos)}"
       f"-seed{seed}" for algos, seed in PARAMS]


def _deployment():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        conf = json.load(f)
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        mix = json.load(f)
    return conf, mix


def _stream(seed, algorithms):
    """The cell's mix cut to a small table (every parameter of
    onehit10m.batch1000 but the call size and the pool's length): the
    restore's items, the oracle's table, two clients' calls."""
    conf, mix = _deployment()
    mix = dict(mix, requests_per_call=ITEMS, pool_calls_per_client=CALLS)
    key_params = dict(conf["key_model"], algorithms=list(algorithms))
    tr = Traffic(mix, key_params, RESIDENTS, seed)
    ids = np.arange(RESIDENTS, dtype=np.uint64)
    rows = tr.model.resident_rows(ids, NOW)
    keys = [bytes(k).decode() for k in
            keymodel.key_bytes(keymodel.HASH_PREFIX, ids)]
    items = [BucketSnapshot(k, *[int(v) for v in row])
             for k, row in zip(keys, rows)]
    table = {k: Row(*[int(v) for v in row]) for k, row in zip(keys, rows)}
    return items, table, _calls(tr) + _calls(tr, client=1)


def _engine(items=None, min_width=WIDTH, capacity=8192):
    eng = Engine(capacity=capacity, min_width=min_width, max_width=WIDTH)
    assert isinstance(eng.directory, NativeKeyDirectory)
    if not eng.supports_columnar():
        pytest.skip("native columnar prep unavailable")
    if items is not None:
        assert eng.load_snapshot(items) == len(items)
    return eng


def _rows(resps):
    return [(int(r.status), r.limit, r.remaining, r.reset_time)
            for r in resps]


def _clock(rng, now):
    """A few milliseconds, sometimes minutes (a leaky bucket of limit 10
    leaks a token in 360 s), after the last group."""
    return now + int(rng.choice((1, 40, 900, 400_000)))


def _serve(eng, entry, windows, now):
    """`windows` (request lists with distinct keys, so nothing is left
    over) through one entry of the served path: rows a window."""
    if entry == "fast":  # Instance.get_rate_limits on a lone call
        return [_rows(eng.get_rate_limits(wk, now_ms=now)) for wk in windows]
    if entry == "windows":  # the combiner's group of request objects
        handle = eng.launch_windows(windows, now_ms=now, staging={})
        assert handle is not None
        return [_rows(wk) for wk in eng.collect_windows(handle)]
    if entry == "columnar":  # the pull loop's lone chunk
        got = []
        for wk in windows:
            outs = _outs(len(wk))
            handle = eng.submit_columnar(*_cols(wk), SLOW, now_ms=now)
            assert handle is not None
            assert not len(eng.complete_columnar(handle, *outs))
            got.append(list(zip(*(o.tolist() for o in outs))))
        return got
    assert entry == "columnar_windows"  # a pull's run of chunks
    handle = eng.launch_columnar_windows(
        [_cols(wk) for wk in windows], SLOW, now_ms=now, staging={})
    assert handle is not None and len(handle[0]) == len(windows)
    assert handle[1] is None
    outs = [_outs(len(wk)) for wk in windows]
    assert not any(len(left) for left in
                   eng.collect_columnar_windows(handle, outs))
    return [list(zip(*(o.tolist() for o in out))) for out in outs]


def _kernels_since(before):
    """{program: windows} that `kernel_telemetry` (process-wide) gained."""
    out = {}
    for (kernel, _w), n in D.kernel_telemetry.counts().items():
        if n != before.get((kernel, _w), 0):
            out[kernel] = out.get(kernel, 0) + n - before.get((kernel, _w), 0)
    return out


def _lean_stats(eng):
    d = eng.stats.as_dict()
    return ({why: d["lean_refused_" + why] for why in D.LEAN_REFUSALS},
            d["lean_tuples"])


def _serve_stream(eng, entry, calls, table, seed):
    """The calls GROUP to a launch (alone where the entry takes one
    window), each answer held to the oracle; every call's rows."""
    rng = np.random.default_rng(seed)
    now, got = NOW + 1, []
    step = 1 if entry in ("fast", "columnar") else GROUP
    for k in range(0, len(calls), step):
        now = _clock(rng, now)
        windows = calls[k:k + step]
        rows = _serve(eng, entry, windows, now)
        expect = [_rows([oracle_answer(table, r, now) for r in wk])
                  for wk in windows]
        assert rows == expect, (entry, k)
        got.extend(rows)
    return got


def test_the_deployment_sends_one_hit_a_request_over_eight_tuples():
    conf, mix = _deployment()
    assert conf["key_model"]["hits"] == [1]
    assert (mix["config"], mix["traffic"]) == (CONFIG, "batch1000")
    _, _, calls = _stream(2**31 + 5, (0, 1))
    reqs = [r for wk in calls for r in wk]
    assert {r.hits for r in reqs} == {1}
    tuples = {(r.limit, r.duration, int(r.algorithm), int(r.behavior))
              for r in reqs}
    assert len(tuples) == 8 <= D.LEAN_MAX_CFG
    assert all(len({r.unique_key for r in wk}) == ITEMS for wk in calls)


@pytest.mark.parametrize("algorithms,seed", PARAMS, ids=IDS)
@pytest.mark.parametrize("entry", ["fast", "columnar", "windows",
                                   "columnar_windows"])
def test_every_launch_rides_the_lean_lane_and_equals_the_oracle(
        entry, algorithms, seed):
    """(a) Residents and never-seen keys (the `fresh` bit), both
    algorithms, the four limits, 24 calls deep: a hot tenant's token
    bucket of limit 10 runs dry, a leaky one leaks between calls."""
    items, table, calls = _stream(seed, algorithms)
    resident_keys = set(table)
    eng = _engine(items)
    before = D.kernel_telemetry.counts()
    got = _serve_stream(eng, entry, calls, table, seed)
    # what served it: lean programs alone, lone windows or scan groups
    kernels = _kernels_since(before)
    lone = entry in ("fast", "columnar")
    assert set(kernels) == {"packed_lean" if lone else "scan_lean"}
    assert sum(kernels.values()) == (
        len(calls) if lone else len(calls) // GROUP * 4)  # depth 3 pads to 4
    refused, tuples = _lean_stats(eng)
    assert not any(refused.values())
    assert 1 <= tuples <= 4 * len(algorithms)
    st = eng.stats
    assert st.requests == len(calls) * ITEMS and st.errors == 0
    # 4 B a lane up and the config table, 16 B a lane back
    launches = len(calls) if lone else len(calls) // GROUP
    depth = 1 if lone else 4
    assert st.staged_bytes == launches * (depth * WIDTH * 4 + 128 * 4 * 8)
    assert st.fetched_bytes == launches * depth * WIDTH * 16
    # the traffic did what the docstring says
    reqs = [r for wk in calls for r in wk]
    assert {r.hash_key() for r in reqs} - resident_keys  # fresh lanes
    flat = [row for rows in got for row in rows]
    assert any(row[0] == 1 for row in flat)  # somebody ran dry
    if 1 in algorithms:  # a leaky bucket's remaining rose between calls
        last, leaked = {}, False
        for r, row in zip(reqs, flat):
            if int(r.algorithm) == 1:
                leaked |= row[2] > last.get(r.unique_key, row[2])
                last[r.unique_key] = row[2]
        assert leaked
    eng.close()


def _req(key, hits=1, limit=100, duration=3_600_000, algorithm=0,
         behavior=0):
    return RateLimitReq(name="oh", unique_key=key, hits=hits, limit=limit,
                        duration=duration, algorithm=Algorithm(algorithm),
                        behavior=behavior)


@pytest.mark.parametrize("algorithms,seed", PARAMS, ids=IDS)
def test_a_repeated_key_rides_the_row_carried_lean_scan(algorithms, seed):
    """(a) A gateway that does not deduplicate: a plan's key stands seven
    times in a call of one-hit requests. On a width ladder (16..64) its
    later occurrences are rounds of a scan group whose rows ride the carry
    (`carry_lean`), and occurrence k is answered as if it came after k-1."""
    eng = _engine(min_width=16)
    rng = np.random.default_rng(seed)
    table = {}
    before = D.kernel_telemetry.counts()
    now = NOW
    for call in range(4):
        now = _clock(rng, now)
        reqs = [_req(f"t{int(j)}", limit=(10, 100, 1000, 100000)[int(j) % 4],
                     algorithm=algorithms[int(j) % len(algorithms)])
                for j in rng.permutation(40)]
        hot = _req("hot", limit=10, algorithm=algorithms[0])
        reqs = [reqs[j] if j < 40 else hot
                for j in rng.permutation(47)]
        got = (eng.get_rate_limits(reqs, now_ms=now) if call % 2
               else eng._slow_window(reqs, now))
        assert _rows(got) == _rows([oracle_answer(table, r, now)
                                    for r in reqs]), call
    kernels = _kernels_since(before)
    assert "carry_lean" in kernels
    assert all(k.endswith("_lean") for k in kernels), kernels
    assert eng.stats.scan_rounds_carried == eng.stats.scan_rounds > 0
    refused, tuples = _lean_stats(eng)
    assert not any(refused.values()) and tuples >= 1
    eng.close()


@pytest.mark.parametrize("algorithms,seed", PARAMS, ids=IDS)
def test_wide_staging_answers_the_same_bit_for_bit(monkeypatch, algorithms,
                                                   seed):
    """(b) GUBER_STAGING=wide pins the i64[9, W] format: other programs,
    18 times the bytes up, the same answers; the lane is not tried, so no
    refusal is counted."""
    items, table, calls = _stream(seed, algorithms)
    lean = _engine(items)
    monkeypatch.setenv("GUBER_STAGING", "wide")
    wide = _engine(items)
    monkeypatch.delenv("GUBER_STAGING")
    assert (lean._staging, wide._staging) == ("auto", "wide")
    before = D.kernel_telemetry.counts()
    got_wide = _serve_stream(wide, "columnar_windows", calls,
                             copy.deepcopy(table), seed)
    assert set(_kernels_since(before)) == {"scan_wide"}
    got_lean = _serve_stream(lean, "columnar_windows", calls, table, seed)
    assert got_lean == got_wide
    assert wide.stats.staged_bytes == 18 * (
        lean.stats.staged_bytes - len(calls) // GROUP * 128 * 4 * 8)
    assert _lean_stats(wide) == ({why: 0 for why in D.LEAN_REFUSALS}, 0)
    for eng in (lean, wide):
        eng.close()


def _off_the_lane(reason, algo):
    """(windows of a group, the program that has to take it): the
    deployment's one-hit requests with one thing the lane cannot carry."""
    windows = [[_req(f"w{n}k{j}", limit=(10, 100, 1000, 100000)[j % 4],
                     algorithm=algo) for j in range(43)] for n in range(3)]
    if reason == "hits":
        windows[1][17] = _req("w1k17", hits=2, limit=100, algorithm=algo)
        return windows, "scan_compact"
    if reason == "tuples":  # 3 x 43 = 129 distinct limits: one too many
        windows = [[_req(f"w{n}k{j}", limit=1 + n * 43 + j, algorithm=algo)
                    for j in range(43)] for n in range(3)]
        return windows, "scan_compact"
    if reason == "range":  # past 31 bits: compact cannot carry it either
        windows[2][0] = _req("w2k0", limit=1 << 31, algorithm=algo)
        return windows, "scan_wide"
    assert reason == "capacity"
    return windows, "scan_compact"


@pytest.mark.parametrize("algo", [0, 1], ids=["token", "leaky"])
@pytest.mark.parametrize("reason", ["hits", "tuples", "range", "capacity"])
def test_what_the_lane_cannot_carry_leaves_it_and_is_counted(reason, algo):
    """(c) The whole launch leaves the lane for one lane's sake, the
    answers stay the oracle's, and the counter names the first reason."""
    windows, program = _off_the_lane(reason, algo)
    eng = _engine()
    if reason == "capacity":
        # a table of 2^24 slots is not allocated in a test: the launch
        # funnel is told the capacity such a table would have
        assert not D.lean_capacity_ok(1 << 24)
        eng.capacity = 1 << 24
    table = {}
    for call in range(3):
        now = NOW + 1 + 900 * call
        before = D.kernel_telemetry.counts()
        got = _serve(eng, "columnar_windows", windows, now)
        assert got == [_rows([oracle_answer(table, r, now) for r in wk])
                       for wk in windows]
        assert _kernels_since(before) == {program: 4}
        refused, _tuples = _lean_stats(eng)
        assert refused == {why: (call + 1 if why == reason else 0)
                           for why in D.LEAN_REFUSALS}
    # with the offending lane out (or the 129th tuple), the launch is lean
    if reason == "tuples":
        windows[2] = windows[2][:-1]
    elif reason == "capacity":
        eng.capacity = 8192
    else:
        windows = [[r for r in wk if r.hits == 1 and r.limit < 1 << 31]
                   for wk in windows]
    before = D.kernel_telemetry.counts()
    now = NOW + 5000
    got = _serve(eng, "columnar_windows", windows, now)
    assert got == [_rows([oracle_answer(table, r, now) for r in wk])
                   for wk in windows]
    assert _kernels_since(before) == {"scan_lean": 4}
    assert _lean_stats(eng)[1] == (128 if reason == "tuples" else 4)
    eng.close()


@pytest.mark.parametrize("algo", [0, 1], ids=["token", "leaky"])
def test_a_gregorian_lane_leaves_the_lane_through_the_object_path(algo):
    """(c) The pull loop hands a gregorian request back as a leftover; the
    object path launches it wide and the refusal is `gregorian`."""
    eng = _engine()
    reqs = [_req(f"g{j}", limit=100, algorithm=algo) for j in range(20)]
    reqs[5] = _req("g5", limit=100, algorithm=algo, behavior=GREG,
                   duration=GREGORIAN_HOURS)
    table = {}
    before = D.kernel_telemetry.counts()
    got = eng._slow_window(reqs, NOW + 1)
    assert _rows(got) == _rows([oracle_answer(table, r, NOW + 1)
                                for r in reqs])
    assert _kernels_since(before) == {"packed_wide": 1}
    refused, tuples = _lean_stats(eng)
    assert refused == {why: int(why == "gregorian")
                       for why in D.LEAN_REFUSALS}
    assert tuples == 0
    eng.close()


def test_the_lanes_ceiling_is_two_to_the_24_less_one():
    """(c) The boundary on the function itself: 0xFFFFFF is the padding
    sentinel, so the largest lean table has 2^24 - 1 slots, 0..2^24 - 2."""
    top = (1 << 24) - 1
    assert D.lean_capacity_ok(top) and not D.lean_capacity_ok(top + 1)
    packed = np.zeros((9, 4), np.int64)
    packed[0] = (0, top - 1, 7, -1)
    packed[1, :3] = 1
    packed[2, :3] = 10
    packed[3, :3] = 1000
    staged, refused, tuples = D.lean_stage(packed, top)
    assert refused is None and tuples == 1
    assert (staged[0] & 0xFFFFFF).tolist() == [0, top - 1, 7, top]
    assert D.lean_stage(packed, top + 1) == (None, "capacity", 0)
    packed[0, 1] = top  # a live lane on the sentinel itself
    assert D.lean_stage(packed, top) == (None, "capacity", 0)
    assert D.lean_window(packed, top) is None


@pytest.mark.parametrize("algo,seed", [(a, s) for a in (0, 1)
                                       for s in (11, 12)],
                         ids=["token-seed11", "token-seed12",
                              "leaky-seed11", "leaky-seed12"])
def test_config_ids_past_64_decode(monkeypatch, algo, seed):
    """(d) 120 plans in one launch: config ids 64..119 set bit 31 of the
    lane word (a negative i32), and every lane still echoes its own
    `limit` and counts against it."""
    rng = np.random.default_rng([seed, algo])
    limits = rng.permutation(np.arange(2, 122)).reshape(2, 60)
    windows = [[_req(f"p{n}k{j}", limit=int(limits[n, j]), algorithm=algo)
                for j in range(60)] for n in range(2)]
    seen = []
    real = engine_mod.lean_stage

    def spy(packed, capacity, width=None):
        out = real(packed, capacity, width)
        seen.append(out)
        return out

    monkeypatch.setattr(engine_mod, "lean_stage", spy)
    eng = _engine()
    table = {}
    for call in range(3):
        now = NOW + 1 + 700 * call
        got = _serve(eng, "columnar_windows", windows, now)
        assert got == [_rows([oracle_answer(table, r, now) for r in wk])
                       for wk in windows]
        assert [row[1] for rows in got for row in rows] == \
            limits.reshape(-1).tolist()
    assert len(seen) == 3
    for (lanes, cfg), refused, tuples in seen:
        assert refused is None and tuples == 120
        live = lanes[:, :60]
        assert (live < 0).sum() == 120 - 64  # bit 31: ids 64..119
        ids = (live.astype(np.int64) >> 25) & 127
        assert sorted(ids.reshape(-1).tolist()) == list(range(120))
        assert (cfg[ids, 0] == limits).all()
    assert _lean_stats(eng) == ({why: 0 for why in D.LEAN_REFUSALS}, 120)
    eng.close()


@pytest.mark.parametrize("algorithms,seed", PARAMS, ids=IDS)
def test_the_ledger_and_the_rows_read_the_same_after_lean_as_after_wide(
        monkeypatch, algorithms, seed):
    """(e) What the daemon reads back of its own table does not depend on
    the lane a launch rode: the decision ledger's totals over the stream
    (its stash reads the wide staging columns, whichever format shipped)
    and the rows `rows_for_keys` point-reads for every key served."""
    items, table, calls = _stream(seed, algorithms)
    engines = {}
    for staging in ("auto", "wide"):
        monkeypatch.setenv("GUBER_STAGING", staging)
        eng = _engine(items)
        eng.ledger = DecisionLedger(enabled=True, key_capacity=4096)
        engines[staging] = eng
    monkeypatch.delenv("GUBER_STAGING")
    # one clock for both: rows_for_keys reads the wall clock for expiry
    clock = {"now": NOW}
    monkeypatch.setattr(engine_mod, "millisecond_now", lambda: clock["now"])
    totals, rows = {}, {}
    keys = sorted({r.hash_key() for wk in calls for r in wk})
    for staging, eng in engines.items():
        before = D.kernel_telemetry.counts()
        _serve_stream(eng, "columnar_windows", calls,
                      copy.deepcopy(table), seed)
        assert set(_kernels_since(before)) == {
            "scan_lean" if staging == "auto" else "scan_wide"}
        led = eng.ledger
        led.audit(eng, now_ms=NOW + 10_000_000, force=True)
        totals[staging] = led.totals()
        found, got = eng.rows_for_keys(keys)
        assert found == keys
        rows[staging] = got
        eng.close()
    assert totals["auto"] == totals["wide"]
    assert totals["auto"]["violations"] == 0
    assert totals["auto"]["attempted"] == len(calls) * ITEMS
    assert np.array_equal(rows["auto"], rows["wide"])

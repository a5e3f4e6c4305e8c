"""The four-chip deployment (benchmarks/configs/mesh-4chip-40m.json) at a
small table on four virtual devices: the benchmark's own seeded traffic
through `ShardedEngine`'s columnar entries against the benchmark's plain
oracle, the four shards against the one-table `Engine`, and the engine's
stamps (obs/profile.py phases, `stats["*_ns"]`, `stats["lanes_max"]`) that
the cell's per-layer readers diff.
"""

import json
import os
import sys

import numpy as np
import pytest

from gubernator_tpu.models.engine import Engine
from gubernator_tpu.obs import profile as profile_mod
from gubernator_tpu.parallel import ShardedEngine, shard_of_key
from gubernator_tpu.service.pb import gubernator_pb2 as pb
from gubernator_tpu.store import BucketSnapshot
from gubernator_tpu.types import Behavior, RateLimitReq

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:  # appended: nothing of tests/ is shadowed
    sys.path.append(BENCH)

import keymodel  # noqa: E402
import oracle  # noqa: E402
from traffic import Traffic  # noqa: E402

NOW = 1_700_000_000_000
SHARDS = 4
RESIDENTS = 3000
ITEMS = 200  # requests a call: ~50 lanes a shard
CALLS = 6
WIDTH = 256
SLOW = (int(Behavior.DURATION_IS_GREGORIAN) | int(Behavior.GLOBAL)
        | int(Behavior.MULTI_REGION))
SERIAL = ("lock_wait", "prep", "dispatch", "readback", "demux")


def _deployment():
    with open(os.path.join(BENCH, "configs", "mesh-4chip-40m.json")) as f:
        conf = json.load(f)
    with open(os.path.join(BENCH, "workloads", "mesh40m.batch1000.json")) as f:
        mix = json.load(f)
    return conf, mix


def _traffic(seed, algorithms):
    """The cell's mix cut to a small table: every parameter of
    mesh40m.batch1000 but the call size and the pool's length."""
    conf, mix = _deployment()
    mix = dict(mix, requests_per_call=ITEMS, pool_calls_per_client=CALLS)
    key_params = dict(conf["key_model"], algorithms=list(algorithms))
    return Traffic(mix, key_params, RESIDENTS, seed)


def _residents(tr):
    """The snapshot the benchmark would restore, as the loader's items and
    as the oracle's table (keyed by the daemon's table key)."""
    ids = np.arange(RESIDENTS, dtype=np.uint64)
    rows = tr.model.resident_rows(ids, NOW)
    keys = [bytes(k).decode() for k in
            keymodel.key_bytes(keymodel.HASH_PREFIX, ids)]
    items = [BucketSnapshot(k, *[int(v) for v in row])
             for k, row in zip(keys, rows)]
    table = {k: oracle.Row(*[int(v) for v in row])
             for k, row in zip(keys, rows)}
    return items, table


def _calls(tr, client=0):
    """One client's pool, decoded from the bytes the load generator would
    send: [[RateLimitReq]]."""
    out = []
    for call in tr.build_pool(client):
        msg = pb.GetRateLimitsReq.FromString(call.body)
        out.append([RateLimitReq(
            name=r.name, unique_key=r.unique_key, hits=r.hits, limit=r.limit,
            duration=r.duration, algorithm=r.algorithm, behavior=r.behavior)
            for r in msg.requests])
    return out


def _cols(reqs):
    names = [r.name.encode() for r in reqs]
    ukeys = [r.unique_key.encode() for r in reqs]
    off = np.zeros(len(reqs) + 1, np.int32)
    np.cumsum([len(a) + len(b) for a, b in zip(names, ukeys)], out=off[1:])
    return (len(reqs), b"".join(a + b for a, b in zip(names, ukeys)), off,
            np.array([len(a) for a in names], np.int32),
            np.array([r.hits for r in reqs], np.int64),
            np.array([r.limit for r in reqs], np.int64),
            np.array([r.duration for r in reqs], np.int64),
            np.array([int(r.algorithm) for r in reqs], np.int32),
            np.array([int(r.behavior) for r in reqs], np.int32))


def _outs(n):
    return (np.zeros(n, np.int32), np.zeros(n, np.int64),
            np.zeros(n, np.int64), np.zeros(n, np.int64))


def _serve_columnar(eng, reqs, now_ms):
    """One window through submit_/complete_columnar, leftovers through the
    object path after it: [(status, limit, remaining, reset_time)]."""
    outs = _outs(len(reqs))
    handle = eng.submit_columnar(*_cols(reqs), SLOW, now_ms=now_ms)
    assert handle is not None
    for i in eng.complete_columnar(handle, *outs).tolist():
        r = eng.get_rate_limits([reqs[i]], now_ms=now_ms)[0]
        for col, v in zip(outs, (r.status, r.limit, r.remaining,
                                 r.reset_time)):
            col[i] = v
    return list(zip(*(c.tolist() for c in outs)))


def _mesh(**kw):
    kw.setdefault("min_width", WIDTH)
    kw.setdefault("max_width", WIDTH)
    return ShardedEngine(n_shards=SHARDS, capacity_per_shard=2048, **kw)


@pytest.mark.parametrize("algorithms", [(0,), (1,), (0, 1)],
                         ids=["token", "leaky", "both"])
@pytest.mark.parametrize("seed", [7, 2**31 + 33])
def test_seeded_traffic_equals_the_oracle_answer_for_answer(seed, algorithms):
    """The benchmark's traffic (Zipf 0.99 over the residents, keys distinct
    in a call, 1% new keys) after the benchmark's restore, on a pinned
    clock: every answer of every call equals benchmarks/oracle.py's."""
    tr = _traffic(seed, algorithms)
    items, table = _residents(tr)
    mesh = _mesh()
    assert mesh.load_snapshot(items) == RESIDENTS
    new_keys = 0
    for k, reqs in enumerate(_calls(tr)):
        now = NOW + 1 + 900 * k
        got = _serve_columnar(mesh, reqs, now)
        for i, r in enumerate(reqs):
            key = r.hash_key()
            new_keys += key not in table
            a = oracle.decide(
                table, key, hits=r.hits, limit=r.limit, duration=r.duration,
                algorithm=int(r.algorithm), behavior=int(r.behavior), now=now)
            assert got[i] == (a.status, a.limit, a.remaining, a.reset_time), \
                (k, i, r)
    assert new_keys > 0  # the mix's 1% of never-seen keys took the insert path
    assert mesh.stats["requests"] == CALLS * ITEMS
    assert mesh.stats["errors"] == 0


@pytest.fixture(scope="module")
def shared_stream():
    """One request stream served by the four shards and by one table."""
    tr = _traffic(2**31 + 5, (0, 1))
    items, _ = _residents(tr)
    mesh = _mesh()
    single = Engine(capacity=SHARDS * 2048, min_width=WIDTH, max_width=WIDTH)
    mesh.load_snapshot(items)
    single.load_snapshot(items)
    calls = _calls(tr) + _calls(tr, client=1)
    answers = {"mesh": [], "single": []}
    for k, reqs in enumerate(calls):
        now = NOW + 1 + 700 * k
        answers["mesh"].append(_serve_columnar(mesh, reqs, now))
        answers["single"].append(_serve_columnar(single, reqs, now))
    yield mesh, single, calls, answers
    single.close()


class TestTheShareAddsUp:
    def test_four_shards_answer_as_one_table(self, shared_stream):
        _, _, calls, answers = shared_stream
        for k in range(len(calls)):
            assert answers["mesh"][k] == answers["single"][k], k

    def test_every_key_is_held_by_the_one_shard_that_owns_it(
            self, shared_stream):
        mesh, _, calls, _ = shared_stream
        held = [dict(d.items()) for d in mesh.directories]
        served = {r.hash_key() for reqs in calls for r in reqs}
        assert served <= set().union(*held)
        assert sum(len(h) for h in held) == len(set().union(*held))
        for key in served:
            assert [o for o, h in enumerate(held) if key in h] == \
                [shard_of_key(key, SHARDS)], key

    def test_the_shards_rows_are_the_one_tables_rows(self, shared_stream):
        mesh, single, _, _ = shared_stream
        rows = {}
        for name, eng in (("mesh", mesh), ("single", single)):
            snap = eng.snapshot(include_expired=True)
            rows[name] = {b.key: (b.algo, b.limit, b.remaining, b.duration,
                                  b.stamp, b.expire_at, b.status)
                          for b in snap}
            assert len(rows[name]) == len(snap)
        assert rows["mesh"] == rows["single"]


# ---- the stamps: one entry of the engine at a time, N windows each


def _reqs(tag, n=ITEMS):
    return [RateLimitReq(name="st", unique_key=f"{tag}:{i}", hits=1,
                         limit=50, duration=60_000) for i in range(n)]


def _drive_submit_complete(mesh, n):
    for k in range(n):
        _serve_columnar(mesh, _reqs(k), NOW + k)


def _drive_columnar_windows(mesh, n):
    for k in range(0, n, 2):  # groups of two windows
        wins = [_cols(_reqs(k)), _cols(_reqs(k + 1))]
        handle = mesh.launch_columnar_windows(wins, SLOW, now_ms=NOW + k)
        assert handle is not None and handle[1] is None
        left = mesh.collect_columnar_windows(
            handle, [_outs(ITEMS), _outs(ITEMS)])
        assert [len(x) for x in left] == [0, 0]


def _drive_fast_window(mesh, n):
    for k in range(n):
        assert len(mesh.get_rate_limits(_reqs(k), now_ms=NOW + k)) == ITEMS


def _drive_object_windows(mesh, n):
    for k in range(0, n, 2):
        handle = mesh.launch_windows([_reqs(k), _reqs(k + 1)], now_ms=NOW + k)
        assert handle is not None
        assert [len(r) for r in mesh.collect_windows(handle)] == [ITEMS] * 2


ENTRIES = {
    "submit_columnar": _drive_submit_complete,
    "launch_columnar_windows": _drive_columnar_windows,
    "fast_window": _drive_fast_window,
    "launch_windows": _drive_object_windows,
}


@pytest.mark.parametrize("site", sorted(ENTRIES))
def test_each_window_is_stamped_once_in_every_serial_phase(site):
    n = 6
    mesh = _mesh()
    ENTRIES[site](mesh, n)
    stats, phases = mesh.stats, mesh.profiler.totals()
    assert stats["batches"] == stats["rounds"] == n
    assert stats["requests"] == n * ITEMS
    for phase in SERIAL:
        assert phases[phase]["n"] == n, phase
        assert phases[phase]["total_ns"] > 0, phase
    assert phases["queue_wait"]["n"] == 0  # the combiner's, not the engine's
    assert mesh.profiler.site_totals()[site]["n"] == n
    # route and pack keep their own clocks and lie inside `prep`; readback
    # is the private device clock less the dispatches
    assert 0 < stats["prep_ns"] and 0 < stats["pack_ns"]
    assert stats["prep_ns"] + stats["pack_ns"] <= phases["prep"]["total_ns"]
    assert stats["device_ns"] == \
        phases["dispatch"]["total_ns"] + phases["readback"]["total_ns"]
    assert stats["demux_ns"] == phases["demux"]["total_ns"]
    # the fullest shard of each window: at least an even share, at most all
    assert stats["requests"] <= stats["lanes_max"] * SHARDS
    assert stats["lanes_max"] <= stats["requests"]
    assert all(isinstance(v, int) for v in stats.values())


def test_the_python_pipeline_is_stamped_too():
    """Duplicate keys fall to _slow_window: its rounds are stamped like
    Engine's, and the fullest lane still bounds the requests."""
    mesh = _mesh()
    reqs = _reqs("dup", 40) * 3  # three occurrences a key: three rounds
    assert len(mesh.get_rate_limits(reqs, now_ms=NOW)) == 120
    stats, phases = mesh.stats, mesh.profiler.totals()
    assert stats["requests"] == 120 and stats["batches"] == 1
    assert phases["dispatch"]["n"] == phases["readback"]["n"] == \
        phases["demux"]["n"] >= 2
    assert mesh.profiler.site_totals()["slow_window"]["n"] == 1
    assert stats["prep_ns"] + stats["pack_ns"] <= phases["prep"]["total_ns"]
    assert stats["requests"] <= stats["lanes_max"] * SHARDS


def test_the_profiler_off_leaves_the_answers_and_the_counters():
    reqs = [_reqs(k) for k in range(3)]
    out = {}
    for enabled in (True, False):
        mesh = _mesh()
        mesh.profiler.enabled = enabled
        out[enabled] = [_serve_columnar(mesh, r, NOW + k)
                        for k, r in enumerate(reqs)]
        assert mesh.stats["batches"] == 3 and mesh.stats["lanes_max"] > 0
        assert mesh.profiler.totals()["prep"]["n"] == (3 if enabled else 0)
    assert out[True] == out[False]


def test_a_capture_gets_the_windows_spans_in_cycle_order(monkeypatch):
    """While a capture runs the seams open one span a phase and close every
    one, early returns included."""
    log = []

    class Span:
        def __init__(self, name):
            self.name = name
            log.append(("open", name))

        def __exit__(self, *exc):
            log.append(("close", self.name))

    monkeypatch.setattr(profile_mod, "_annotation", Span)
    mesh = _mesh()
    mesh.profiler._capturing = True
    _serve_columnar(mesh, _reqs("cap"), NOW)
    # the funnels' own chains run inside the phase they are part of
    inside = {"dispatch": ("stage", "launch"),
              "readback": ("device_wait", "fetch")}
    want = []
    for n in SERIAL:
        want.append(("open", n))
        want += [(kind, sub) for sub in inside.get(n, ())
                 for kind in ("open", "close")]
        want.append(("close", n))
    assert log == want
    # a window wider than the ladder is refused before any stamp
    del log[:]
    assert mesh.submit_columnar(*_cols(_reqs("wide", WIDTH + 1)), SLOW,
                                now_ms=NOW) is None
    assert log == []


def test_the_instance_meters_the_backends_own_profiler():
    from gubernator_tpu.service.config import InstanceConfig
    from gubernator_tpu.service.instance import Instance

    mesh = _mesh()
    inst = Instance(InstanceConfig(backend=mesh), advertise_address="self")
    try:
        assert inst.profiler is mesh.profiler
        _serve_columnar(mesh, _reqs("inst"), NOW)
        body = inst.profiler.endpoint_body()
        assert body["phases"]["readback"]["n"] == 1
    finally:
        inst.close()

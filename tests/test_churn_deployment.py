"""The churn deployment (upstream's benchmark_test.go: every request a key
never seen, against an LRU table that is always full) at a small table:
`Engine` behind the native directory, held answer for answer to the plain
LRU reference (tests/lru_reference.py), whose arithmetic is
gubernator_tpu/ops/oracle.py's.

The benchmark's checker knows no eviction, so on the chip the cell
`churn10m.newkeys1000` holds a never-seen key's first answer and nothing
else of this (PERF.md section 7); it is held here:

(a) every request a new key through several turnovers of the table, across
    tombstone rebuilds of the directory's bucket array;
(b) keys asked again before and after their eviction;
(c) windows and calls whose keys outnumber the free and unpinned slots;
(d) the table never exceeds its capacity, and `evictions`, `inserts` and
    `rebuilds` read what the reference counts;
(e) the decision ledger reports no violation for a tracked key that is
    evicted and comes back as a new bucket;
(f) the cartographer's harvest of a full table under churn says what a
    plain sort of the hit column says.
"""

import numpy as np
import pytest

from gubernator_tpu import RateLimitReq
from gubernator_tpu.models.engine import Engine
from gubernator_tpu.native import NativeKeyDirectory
from gubernator_tpu.obs import keyspace
from gubernator_tpu.obs.introspect import _backend_vars
from gubernator_tpu.obs.ledger import DecisionLedger
from gubernator_tpu.ops.decide import ROW_HITS, fetch_column

from lru_reference import LruReference

WIDTH = 64  # a one-width ladder, as the configuration's (8192 there)
NOW = 1_760_000_000_000
DURATION = 60_000
LIMITS = (3, 5, 10, 100)
PARAMS = [(algo, seed) for algo in (0, 1) for seed in (11, 12)]
IDS = [f"{'token' if a == 0 else 'leaky'}-seed{s}" for a, s in PARAMS]


class Node:
    """An engine and its reference, fed the same calls at the same clock."""

    def __init__(self, capacity, algo, seed, width=WIDTH):
        self.eng = Engine(capacity=capacity, min_width=width, max_width=width)
        assert isinstance(self.eng.directory, NativeKeyDirectory)
        self.ref = LruReference(capacity, self.eng.max_width)
        self.algo = algo
        self.rng = np.random.default_rng([seed, algo, capacity])
        self.now = NOW
        self.next_id = 0
        self.calls = 0

    def request(self, key_id: int) -> RateLimitReq:
        """A key's request is the same every time it is asked: limit and
        hits are drawn from its id."""
        return RateLimitReq(
            name="rl", unique_key=f"edge:{key_id:08x}",
            hits=1 + key_id % 3, limit=LIMITS[key_id % len(LIMITS)],
            duration=DURATION, algorithm=self.algo)

    def new_ids(self, n: int):
        ids = list(range(self.next_id, self.next_id + n))
        self.next_id += n
        return ids

    def call(self, key_ids):
        """One call of distinct keys, a few milliseconds (sometimes
        seconds: a leaky bucket leaks) after the last; every answer must
        equal the reference's."""
        assert len(set(key_ids)) == len(key_ids)
        self.now += int(self.rng.choice((1, 3, 17, 7_000)))
        reqs = [self.request(k) for k in key_ids]
        got = self.eng.get_rate_limits(reqs, self.now)
        want = self.ref.apply(reqs, self.now)
        rows = [(int(r.status), r.limit, r.remaining, r.reset_time, r.error)
                for r in got]
        assert rows == [(int(r.status), r.limit, r.remaining, r.reset_time,
                         r.error) for r in want], \
            f"call {self.calls} ({len(reqs)} keys) at {self.now}"
        self.calls += 1
        # (d), after every call
        assert len(self.eng.directory) == len(self.ref) <= self.eng.capacity
        return rows

    def counters(self):
        v = _backend_vars(self.eng)
        return dict(v["directory"], requests=v["stats"]["requests"],
                    key_table_size=v["key_table_size"])

    def nbuckets(self) -> int:
        n = 16
        while n < 2 * self.eng.capacity:
            n <<= 1
        return n


@pytest.mark.parametrize("algo,seed", PARAMS, ids=IDS)
def test_every_request_a_new_key_through_several_turnovers(algo, seed):
    """(a) Calls of 1..64 keys (one window, the native one-pass prep) and
    of 65..200 (cut into windows by the python pipeline), every key new,
    until the 256-slot table has turned over five times. A rebuild of the
    bucket array comes every `nbuckets / 4` = 128 tombstones, so several
    fall inside; every answer is a new bucket's."""
    node = Node(256, algo, seed)
    while node.next_id < 6 * 256:
        n = int(node.rng.integers(1, 65)) if node.rng.random() < 0.7 \
            else int(node.rng.integers(65, 201))
        for status, limit, remaining, _reset, error in node.call(
                node.new_ids(n)):
            assert (status, error) == (0, "") and 0 <= remaining < limit
    c = node.counters()
    assert c["rebuilds"] >= 3
    assert c["evictions"] == node.ref.evictions == node.next_id - 256
    assert c["inserts"] == c["requests"] == node.next_id


@pytest.mark.parametrize("algo,seed", PARAMS, ids=IDS)
def test_keys_asked_again_before_and_after_their_eviction(algo, seed):
    """(b) A working set smaller than the table is asked again and again
    (its buckets drain, over the limit included) while new keys stream
    past; every so often the stream outruns the table and evicts part of
    the working set, which then answers as new buckets."""
    node = Node(256, algo, seed)
    working = node.new_ids(40)
    reborn = 0
    for round_ in range(60):
        again = [k for k in working if node.rng.random() < 0.6]
        before = {k: f"rl_edge:{k:08x}" in node.ref for k in again}
        mixed = again + node.new_ids(int(node.rng.integers(0, 24)))
        node.rng.shuffle(mixed)
        node.call(mixed[:WIDTH])
        reborn += sum(1 for k in again[:WIDTH] if not before[k])
        if round_ % 12 == 11:  # a burst longer than the table
            for _ in range(5):
                node.call(node.new_ids(60))
    c = node.counters()
    assert reborn >= 20  # keys did come back after their eviction
    assert node.ref.evictions > 256 and c["evictions"] == node.ref.evictions
    assert c["inserts"] == node.ref.fresh < c["requests"]
    assert c["rebuilds"] >= 1


@pytest.mark.parametrize("algo,seed", PARAMS, ids=IDS)
def test_a_window_whose_keys_outnumber_the_free_and_unpinned_slots(
        algo, seed):
    """(c) A table exactly one window wide: a window of 64 distinct keys
    pins every slot it has touched, so its new keys can only take what the
    keys before them in the window left unpinned, and a resident that
    stands late in the window is evicted by an earlier lane and comes back
    as a new bucket in its turn. Then calls of several windows, each more
    keys than the table holds, so a call evicts its own first keys."""
    node = Node(WIDTH, algo, seed)
    residents = node.new_ids(WIDTH)
    node.call(residents)
    for _ in range(12):
        keep = [k for k in residents if node.rng.random() < 0.5]
        window = keep + node.new_ids(WIDTH - len(keep))
        node.rng.shuffle(window)
        node.call(window)
        residents = window
    assert node.ref.evictions and len(node.ref) == WIDTH
    big = Node(256, algo, seed)
    first = big.new_ids(200)
    big.call(first)
    for _ in range(6):
        # 5 windows of 64 on a 256-slot table: the call's last window
        # evicts keys of its first
        call = big.new_ids(250) + [k for k in first
                                   if big.rng.random() < 0.35]
        big.rng.shuffle(call)
        big.call(call)
        first = call[:200]
    for n in (node, big):
        c = n.counters()
        assert c["evictions"] == n.ref.evictions
        assert c["inserts"] == n.ref.fresh


@pytest.mark.parametrize("algo,seed", PARAMS, ids=IDS)
def test_the_counters_read_what_the_reference_counts(algo, seed):
    """(d) `engine.directory` of /v1/debug/vars (and `requests`) beside
    the reference's own counts, at a 1024-slot table restored full (the
    configuration's `resident_keys` = slots): the first request evicts."""
    capacity = 1024
    node = Node(capacity, algo, seed)
    residents = node.new_ids(capacity)
    for lo in range(0, capacity, WIDTH):
        node.call(residents[lo:lo + WIDTH])
    base = node.counters()
    assert base["evictions"] == 0 and base["rebuilds"] == 0
    assert base["key_table_size"] == capacity
    assert base["rebuild_ns"] == base["rebuild_max_ns"] == 0
    turned = 0
    while turned < 3 * capacity:
        n = int(node.rng.integers(1, WIDTH + 1))
        node.call(node.new_ids(n))
        turned += n
        c = node.counters()
        assert c["evictions"] - base["evictions"] == turned
        assert c["key_table_size"] == capacity
    assert c["evictions"] == node.ref.evictions
    assert c["inserts"] == node.ref.fresh == c["requests"]
    # the reference keeps no hash table, so of the rebuilds it can say only
    # what tombstones allow: one needs more than nbuckets / 4 of them, and
    # an eviction makes at most one
    per_rebuild = node.nbuckets() // 4 + 1
    assert 1 <= c["rebuilds"] <= c["evictions"] // per_rebuild
    assert 0 < c["rebuild_max_ns"] <= c["rebuild_ns"]
    assert c["rebuild_ns"] <= c["rebuilds"] * c["rebuild_max_ns"]
    # the order of the victims is the residents' own: least recent first
    assert node.ref.evicted[:capacity] == [
        f"rl_edge:{k:08x}" for k in residents]


@pytest.mark.parametrize("algo,seed", PARAMS, ids=IDS)
def test_the_ledger_reports_no_violation_for_a_key_that_was_evicted(
        algo, seed):
    """(e) The ledger tracks the first keys it meets. They drain their
    buckets, are evicted under it (audits tick while distinct slots stream
    past, most of them recycled before the tick), and come back as new
    buckets that admit again inside the old bucket's duration: that is the
    eviction the deployment accepts, not budget the node minted."""
    node = Node(256, algo, seed)
    led = DecisionLedger(enabled=True, key_capacity=32)
    node.eng.ledger = led
    tracked = node.new_ids(32)
    for _ in range(6):  # drain: limits 3..100, hits 1..3
        node.call(tracked)
    led.audit(node.eng, now_ms=node.now)
    assert led.totals()["keys_tracked"] == 32
    for _lap in range(4):
        for _ in range(6):  # 360 new keys: the table turns over
            node.call(node.new_ids(60))
            if node.rng.random() < 0.5:
                led.audit(node.eng, now_ms=node.now)
        assert not any(f"rl_edge:{k:08x}" in node.ref for k in tracked)
        rows = node.call(tracked)
        assert all(r[0] == 0 and r[2] < r[1] for r in rows)  # new buckets
        for _ in range(3):
            node.call(tracked)
        led.audit(node.eng, now_ms=node.now)
    led.audit(node.eng, now_ms=node.now + 2 * DURATION, force=True)
    totals = led.totals()
    assert totals["violations"] == 0, led.debug()["recent_violations"]
    assert totals["attempted"] > 0


@pytest.mark.parametrize("algo,seed", PARAMS, ids=IDS)
def test_a_harvest_of_the_full_table_reads_what_a_plain_sort_reads(
        algo, seed, monkeypatch):
    """(f) Every slot holds a key and carries its hits since it last
    changed hands (a fresh lane restarts the row's counter), and evictions
    rise between harvests. The harvest walks the column a chunk at a time
    (a million rows: never reached at this size), so it is read again in
    chunks of 48 rows, heaviest slots merged from chunk to chunk."""
    node = Node(256, algo, seed)
    for _ in range(3):  # heavy hitters, asked often enough to stay
        heavy = node.new_ids(5)
        for _ in range(int(node.rng.integers(3, 9))):
            node.call(heavy)
        for _ in range(6):
            node.call(node.new_ids(int(node.rng.integers(20, 60))))

    class _Holder:
        backend = node.eng

    carto = keyspace.KeyspaceCartographer(_Holder(), top_k=8)
    report = carto.harvest()
    counts = fetch_column(node.eng.state, ROW_HITS)
    ranked = np.sort(counts)[::-1].astype(np.float64)
    mass = report["hit_mass"]
    assert mass["nonzero_slots"] == int((counts > 0).sum()) == 256
    assert mass["tracked_hits"] == int(counts.sum())
    for n in (1, 10, 100):
        assert mass[f"top{n}_share"] == pytest.approx(
            ranked[:n].sum() / ranked.sum(), rel=1e-12)
    assert report["occupancy"]["free_slots"] == 0
    assert report["evictions"]["total"] == node.ref.evictions > 256
    top = report["top_keys"]
    assert [e["hits"] for e in top] == ranked[:8].tolist()
    assert all(counts[e["slot"]] == e["hits"] for e in top)
    assert all(e["key"] in node.ref for e in top)
    monkeypatch.setattr(keyspace, "_CHUNK", 48)
    chunked = carto.harvest()
    assert chunked["hit_mass"] == mass
    assert [e["hits"] for e in chunked["top_keys"]] == ranked[:8].tolist()

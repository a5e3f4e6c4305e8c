"""The hot-tenant deployment (benchmarks/configs/node-1chip-10m-hot.json)
at a small table: the benchmark's own seeded traffic for its cell
(benchmarks/workloads/hot10m.repeats1000.json: Zipf 0.99, a key many times
in one call; and the same mix at a steeper 1.2, where repeats are most of a
call and a key needs more than one scan group whatever the seed) after the benchmark's restore, through the path the daemon takes —
the pull loop's `_handle_batch`, `submit_`/`complete_columnar` for a chunk's
first occurrences (`_columnar_chunk_lockstep`), `_leftover_items` ->
`Instance.get_rate_limits` -> the combiner -> `Engine.launch_windows` ->
the duplicate-key rounds for the rest — one caller and one pull worker, so
the order is known.

- every answer of every call equals benchmarks/oracle.py's, in the call's
  order: occurrence k of a key is answered as if it came after k-1;
- the width ladder changes the launched shapes and never an answer;
- the meters on that path add up: `leftover_items`, the `leftover` phase
  and span, `scan_dispatches`, `scan_rounds`,
  `scan_lanes_live`, `scan_lanes`.
"""

import json
import os
import sys

import numpy as np
import pytest

from gubernator_tpu.models.engine import Engine
from gubernator_tpu.service.metrics import Metrics
from gubernator_tpu.service.peerlink import METHOD_GET_RATE_LIMITS
from gubernator_tpu.store import BucketSnapshot

from test_mesh_deployment import _calls  # a pool decoded from its bytes
from test_columnar_pipeline import (
    NOW,
    _serve,
    chunk_cap,
    pin_engine_clock,
    send_as_one_pull,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:  # appended: nothing of tests/ is shadowed
    sys.path.append(BENCH)

import keymodel  # noqa: E402
import oracle  # noqa: E402
from traffic import Traffic  # noqa: E402

CONFIG = "node-1chip-10m-hot"
CELL = "hot10m.repeats1000"
RESIDENTS = 3000
ITEMS = 300  # requests a call, and the chunk cap: a call is one chunk
CALLS = 6
PER_PULL = 3  # frames sent together: the last two reach the worker as one pull
WIDEST = 512
LADDER = 64  # the ladder's bottom: 64, 128, 256, 512
STEEP = 1.2  # a skew at which repeats are most of a call (the cell's: 0.99)


def _config(name=CONFIG):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _mix():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        return json.load(f)


def _traffic(seed, algorithms=(0, 1), **key_model):
    """The cell's mix cut to a small table: every parameter of
    hot10m.repeats1000 but the call size and the pool's length."""
    mix = _mix()
    mix = dict(mix, requests_per_call=ITEMS, pool_calls_per_client=CALLS,
               key_model=dict(mix["key_model"], **key_model))
    key_params = dict(_config()["key_model"], algorithms=list(algorithms))
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


class _Node:
    """One Engine behind an Instance and the pull loop, one pull worker,
    one connection; chunks are cut at ITEMS as MAX_BATCH_SIZE cuts a pull
    of 1000-request calls."""

    def __init__(self, monkeypatch, min_width, residents=None):
        chunk_cap(monkeypatch, ITEMS)
        self.clock = pin_engine_clock(monkeypatch)
        self.engine = Engine(capacity=8192, min_width=min_width,
                             max_width=WIDEST)
        if not self.engine.supports_columnar():
            pytest.skip("native columnar prep unavailable")
        if residents is not None:
            assert self.engine.load_snapshot(residents) == RESIDENTS
        self.metrics = Metrics()
        self.instance, self.service, self.client = _serve(
            self.engine, workers=1, metrics=self.metrics)
        self.widest_pull = 0  # items of the largest pull served
        # what the engine launched, counted beside its own counters
        self.launched = {"single": 0, "scan": 0, "scan_rounds": 0,
                         "carried_rounds": 0, "scan_live": 0,
                         "scan_lanes": 0, "shapes": set()}
        single, scan = (self.engine._dispatch_staged,
                        self.engine._dispatch_scan_staged)

        def spy_single(packed, now_ms, live=None):
            self.launched["single"] += 1
            self.launched["shapes"].add(packed.shape)
            return single(packed, now_ms, live)

        def spy_scan(stacked, now_ms, carried=False, live=None, width=None):
            # the stack may hold its live lanes alone: `width` is launched
            launched = (len(stacked), 9, width or stacked.shape[2])
            held = stacked[:, 0, :] >= 0
            self.launched["scan"] += 1
            self.launched["scan_rounds"] += int(held.any(axis=1).sum())
            if carried:  # a lane holds one slot through the stack
                self.launched["carried_rounds"] += int(
                    held.any(axis=1).sum())
                slots = np.where(held, stacked[:, 0, :], -1)
                assert ((slots == slots.max(axis=0)) | ~held).all()
            self.launched["scan_live"] += int(held.sum())
            self.launched["scan_lanes"] += launched[0] * launched[2]
            self.launched["shapes"].add(launched)
            return scan(stacked, now_ms, carried, live, width)

        self.engine._dispatch_staged = spy_single
        self.engine._dispatch_scan_staged = spy_scan

    def pull(self, frames, k):
        """`frames` in one pull at call-clock k: their answers as
        (status, limit, remaining, reset_time) rows, no error among them."""
        self.clock["now"] = NOW + 1 + 900 * k
        pulls = []
        got = send_as_one_pull(self.service, self.client, frames,
                               methods=[METHOD_GET_RATE_LIMITS] * len(frames),
                               pulls=pulls)
        self.widest_pull = max([self.widest_pull]
                               + [n_items for _, n_items, _ in pulls])
        assert not [r.error for out in got for r in out if r.error]
        return [[(int(r.status), r.limit, r.remaining, r.reset_time)
                 for r in out] for out in got]

    def close(self):
        self.client.close()
        self.service.close()
        self.instance.close()


def _serve_pool(node, calls, per_pull=PER_PULL):
    """The pool's calls, `per_pull` frames to a pull (so a hot key's
    repeats straddle the chunks of one pull): every call's rows."""
    rows = []
    for k in range(0, len(calls), per_pull):
        rows.extend(node.pull(calls[k:k + per_pull], k))
    return rows


def _oracle_rows(table, calls, per_pull=PER_PULL):
    rows = []
    for k, reqs in enumerate(calls):
        now = NOW + 1 + 900 * (k - k % per_pull)
        out = []
        for r in reqs:
            a = oracle.decide(
                table, r.hash_key(), hits=r.hits, limit=r.limit,
                duration=r.duration, algorithm=int(r.algorithm),
                behavior=int(r.behavior), now=now)
            out.append((a.status, a.limit, a.remaining, a.reset_time))
        rows.append(out)
    return rows


def _most_repeats(reqs):
    keys = [r.unique_key for r in reqs]
    return max(keys.count(k) for k in set(keys))


def test_the_configuration_is_the_node_file_but_for_skew_and_ladder():
    hot, node = _config(), _config("node-1chip-10m")
    for key in ("chips", "daemon_env", "resident_keys", "table", "key_model",
                "pipeline_depth", "reduced", "rehearse"):
        want = node[key]
        if key == "pipeline_depth":  # its `why` points at the node file's
            want = dict(want, why=hot[key]["why"])
        assert hot[key] == want, key
    for key, text in node["guarantees"].items():
        assert hot["guarantees"][key] == text
    assert "in the order they stand" in hot["guarantees"]["order"]
    assert set(node["assumed"]) < set(hot["assumed"])
    assert hot["source"] != node["source"] and len(hot["source"]) <= 200
    ladder = hot["compile_ladder"]
    assert ladder["shipped"] == node["compile_ladder"]["shipped"] == "64"
    # the lowest bottom whose cold compile fits a run (the file's `why`)
    assert ladder["GUBER_MIN_BATCH_WIDTH"] in ("512", "1024", "2048")
    assert int(ladder["GUBER_MIN_BATCH_WIDTH"]) < int(
        hot["daemon_env"]["GUBER_MAX_BATCH_WIDTH"])


def test_the_mix_is_batch1000s_but_for_skew_and_repeats():
    """And the skew the configuration states is the one the generator
    reads from the mix."""
    mix = _mix()
    with open(os.path.join(BENCH, "workloads", "node10m.batch1000.json")) as f:
        base = json.load(f)
    labels = ("config", "traffic", "who", "why", "key_model")
    assert {k: v for k, v in mix.items() if k not in labels} == \
        {k: v for k, v in base.items() if k not in labels}
    # the skew is the source's own (YCSB's 0.99, the node file's): the
    # mix differs by what upstream's batching bears out, repeats in a call
    assert mix["key_model"] == dict(base["key_model"],
                                    distinct_in_call=False)
    population = _config()["population"]
    assert mix["config"] == CONFIG
    assert mix["key_model"]["zipf_exponent"] == population["zipf_exponent"]
    assert mix["key_model"]["distinct_in_call"] is \
        (not population["repeats_in_call"])


@pytest.mark.parametrize("algorithms", [(0,), (1,), (0, 1)],
                         ids=["token", "leaky", "both"])
@pytest.mark.parametrize("seed", [7, 2**31 + 33])
@pytest.mark.parametrize("skew", [None, STEEP], ids=["the_cells", "steep"])
def test_repeats_in_a_call_equal_the_oracle_answer_for_answer(
        monkeypatch, skew, seed, algorithms):
    """The cell's traffic after the benchmark's restore, several calls to
    a pull: a hot key stands more than 32 times in a call (more than one
    scan group) and its repeats straddle the chunks of one pull."""
    tr = _traffic(seed, algorithms,
                  **({} if skew is None else {"zipf_exponent": skew}))
    items, table = _residents(tr)
    calls = _calls(tr)
    assert all(len(reqs) == ITEMS for reqs in calls)
    assert max(map(_most_repeats, calls)) > (
        Engine._MAX_SCAN if skew else Engine._MAX_SCAN // 2)
    assert {r.unique_key for r in calls[0]} & {r.unique_key for r in calls[1]}
    node = _Node(monkeypatch, LADDER, items)
    try:
        got = _serve_pool(node, calls)
        assert node.widest_pull >= 2 * ITEMS  # two chunks in one pull
        want = _oracle_rows(table, calls)
        for k, (g, w) in enumerate(zip(got, want)):
            for i in range(ITEMS):
                assert g[i] == w[i], (k, i, calls[k][i])
        st = node.engine.stats
        assert st.requests == CALLS * ITEMS and st.errors == 0
        assert st.scan_dispatches > 0
        if skew:  # a call's hottest key alone needs more than one group
            assert st.scan_rounds > st.scan_dispatches * Engine._MAX_SCAN / 2
        new_keys = {r.hash_key() for reqs in calls for r in reqs} \
            - {it.key for it in items}
        assert new_keys  # the mix's 1% of never-seen keys were inserted
    finally:
        node.close()


@pytest.fixture(scope="module")
def served():
    """One stream served at the ladder 64..512 and at one width, at the
    steeper skew, so that most of its lanes take the metered path."""
    mp = pytest.MonkeyPatch()
    tr = _traffic(2**31 + 5, zipf_exponent=STEEP)
    items, _ = _residents(tr)
    calls = _calls(tr) + _calls(tr, client=1)
    out = {}
    try:
        for name, min_width in (("ladder", LADDER), ("one_width", WIDEST)):
            node = _Node(mp, min_width, items)
            try:
                out[name] = (_serve_pool(node, calls), node)
            finally:
                node.close()
        yield calls, out
    finally:
        mp.undo()


class TestTheLadderChangesShapesNeverAnswers:
    def test_the_same_stream_gives_the_same_answers(self, served):
        calls, out = served
        assert len(out["ladder"][0]) == len(calls)
        assert out["ladder"][0] == out["one_width"][0]

    def test_a_round_rides_the_ladders_bottom(self, served):
        _, out = served
        ladder, one = out["ladder"][1], out["one_width"][1]
        assert {s[-1] for s in one.launched["shapes"]} == {WIDEST}
        widths = {s[-1] for s in ladder.launched["shapes"]}
        assert LADDER in widths and len(widths) > 1
        # scan groups are min_width wide whatever they hold
        assert {s[2] for s in ladder.launched["shapes"] if len(s) == 3} \
            == {LADDER}
        assert ladder.engine.stats.scan_lanes \
            < one.engine.stats.scan_lanes
        assert ladder.engine.stats.scan_lanes_live <= \
            one.engine.stats.scan_lanes_live  # wider rounds scan more

    @pytest.mark.parametrize("which", ["ladder", "one_width"])
    def test_the_counters_add_up(self, served, which):
        calls, out = served
        node = out[which][1]
        st, link, seen = node.engine.stats, node.service.stats, node.launched
        n_items = sum(map(len, calls))
        firsts = sum(len({r.unique_key for r in reqs}) for reqs in calls)
        # leftover lanes + columnar lanes = items
        assert link["requests"] == st.requests == n_items
        assert link["leftover_items"] == n_items - firsts > 0.5 * n_items
        # rounds in scans + rounds on a launch of their own = rounds
        assert st.scan_dispatches == seen["scan"] > 0
        assert st.scan_rounds == seen["scan_rounds"]
        # a repeated key's rounds are nested: below max_width every one
        # rode the carry, at it (one program a shape) the table
        assert st.scan_rounds_carried == seen["carried_rounds"] \
            == (st.scan_rounds if which == "ladder" else 0)
        assert st.rounds - st.scan_rounds == seen["single"]
        assert st.scan_lanes_live == seen["scan_live"]
        assert st.scan_lanes == seen["scan_lanes"]
        assert 0 < st.scan_lanes_live <= link["leftover_items"]
        assert st.scan_rounds <= st.scan_dispatches * Engine._MAX_SCAN
        d = st.as_dict()
        assert [d[k] for k in ("scan_dispatches", "scan_rounds",
                               "scan_rounds_carried", "scan_lanes_live",
                               "scan_lanes")] == \
            [st.scan_dispatches, st.scan_rounds, st.scan_rounds_carried,
             st.scan_lanes_live, st.scan_lanes]
        assert all(isinstance(v, int) for v in d.values())

    def test_the_phase_and_the_family_say_the_same(self, served):
        calls, out = served
        node = out["ladder"][1]
        link = node.service.stats
        phase = node.instance.profiler.endpoint_body()["phases"]["leftover"]
        assert phase["n"] == len(calls)  # each chunk handed some back
        assert phase["total_ns"] > 0
        # the leftover stretch holds the combiner's wait and the rounds
        totals = node.instance.profiler.totals()
        assert "leftover" not in totals  # outside the decomposition
        assert totals["queue_wait"]["n"] >= phase["n"]
        text = node.metrics.render(node.instance).decode()
        line = [ln for ln in text.splitlines()
                if ln.startswith("peerlink_leftover_items_total ")]
        assert line and float(line[0].split()[1]) == link["leftover_items"]


def test_keys_distinct_in_a_call_hand_nothing_back_and_scan_as_groups(
        monkeypatch):
    """`node10m.batch1000`'s shape: nothing is handed back, a call is one
    window and one round, and the only scans are the pull loop's groups
    (service/peerlink.py _columnar_run): the calls pulled together, one
    round a call on the table's carry, launched `max_width` wide."""
    tr = _traffic(11, distinct_in_call=True, zipf_exponent=0.99)
    items, table = _residents(tr)
    calls = _calls(tr)
    node = _Node(monkeypatch, LADDER, items)
    try:
        assert _serve_pool(node, calls) == _oracle_rows(table, calls)
        link, st = node.service.stats, node.engine.stats
        assert link["leftover_items"] == 0 and link["columnar_cuts"] == 0
        assert st.rounds == st.batches == CALLS
        grouped = link["columnar_windows"]
        assert 0 < grouped <= CALLS and link["columnar_groups"] > 0
        assert st.scan_dispatches == link["columnar_groups"]
        assert st.scan_rounds == grouped and st.scan_rounds_carried == 0
        assert st.scan_lanes_live == grouped * ITEMS
        assert st.scan_lanes % WIDEST == 0  # max_width wide, pads counted
        assert {s for s in node.launched["shapes"] if len(s) == 3} \
            <= {(k, 9, WIDEST) for k in (2, 4, 8)}
        body = node.instance.profiler.endpoint_body()
        assert body["phases"]["leftover"]["n"] == 0
    finally:
        node.close()


def test_the_profiler_off_leaves_the_answers_and_the_counters(monkeypatch):
    tr = _traffic(13)
    items, _ = _residents(tr)
    calls = _calls(tr)[:2]
    out = {}
    for enabled in (True, False):
        node = _Node(monkeypatch, LADDER, items)
        try:
            node.instance.profiler.enabled = enabled
            out[enabled] = _serve_pool(node, calls)
            assert node.service.stats["leftover_items"] > 0
            assert node.engine.stats.scan_dispatches > 0
            phase = node.instance.profiler.endpoint_body()["phases"]
            assert phase["leftover"]["n"] == (2 if enabled else 0)
        finally:
            node.close()
    assert out[True] == out[False]


def test_a_capture_gets_a_leftover_span_a_chunk(monkeypatch):
    """While a capture runs each chunk that hands back leftovers opens one
    `leftover` host span and closes it."""
    log = []

    class Span:
        def __init__(self, name, **args):
            self.name = name

        def __enter__(self):
            log.append(("open", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("close", self.name))

    tr = _traffic(17)
    items, _ = _residents(tr)
    node = _Node(monkeypatch, LADDER, items)
    try:
        # what Profiler.span() and the seams enter while a capture runs
        monkeypatch.setattr("jax.profiler.TraceAnnotation", Span)
        node.instance.profiler._capturing = True
        node.pull(_calls(tr)[:2], 0)
        node.instance.profiler._capturing = False
        spans = [e for e in log if e[1] == "leftover"]
        assert spans == [("open", "leftover"), ("close", "leftover")] * 2
    finally:
        node.close()


def test_every_scan_shape_the_combiner_launches_is_one_the_warm_up_compiled():
    """On a ladder `warmup()` compiles the row-carried scan depths at the
    bottom width only and `warmup_pipeline` the table-carried ones at the
    top width only, and `launch_windows` launches a group of K > 1 windows
    (table-carried: different callers' keys at arbitrary lanes) at the
    group's own bucket width. The combiner cannot reach a width between the
    two: it opens a second window only when the next submission would
    overflow the first, so one of the two holds more than half of
    `max_width` and the group launches `max_width` wide; two callers'
    leftovers merge into ONE window, whose single launch is warmed at every
    width, and its repeats retire in `min_width` scans on the carry (their
    rounds are nested). So after the two warm-ups no scan program compiles,
    carried or not."""
    import threading

    from gubernator_tpu.service.combiner import BackendCombiner
    from gubernator_tpu.types import RateLimitReq

    lo, hi, scan = 8, 64, 8
    eng = Engine(capacity=4096, min_width=lo, max_width=hi)
    if not eng.supports_pipeline():
        pytest.skip("native prep unavailable")
    eng.warmup()
    eng.warmup_pipeline(max_group=scan)
    programs = eng._scans + eng._scans_carried
    compiled = [fn._cache_size() for fn in programs]
    warmed = {((k, 9, lo), True) for k in (2, 4, 8, 16, 32)} \
        | {((k, 9, hi), False) for k in (2, 4, 8)}
    shapes, real = [], eng._dispatch_scan_staged
    eng._dispatch_scan_staged = lambda stacked, now_ms, carried=False, \
        live=None, width=None: (
        shapes.append(((len(stacked), 9, width or stacked.shape[2]),
                       carried)),
        real(stacked, now_ms, carried, live, width))[1]
    gate, launch = threading.Event(), eng.launch_windows
    eng.launch_windows = lambda *a, **kw: (gate.wait(10), launch(*a, **kw))[1]
    comb = BackendCombiner(eng, depth=3, scan=scan)
    rng = np.random.default_rng(5)
    try:
        for trial in range(6):
            sizes = rng.integers(1, hi + 1, size=12).tolist()
            futs = []
            gate.clear()  # the combiner's launch waits: the rest pile up
            for n, size in enumerate(sizes):
                # even trials: distinct keys (whole groups scan); odd ones
                # open every submission with one key four times (each
                # window cuts, its repeats retire in rounds)
                futs.append(comb.submit_async([RateLimitReq(
                    name="w", unique_key="hot" if trial % 2 and i < 4
                    else f"t{trial}s{n}k{i}",
                    hits=1 + (i + n) % 3, limit=1000, duration=60_000)
                    for i in range(size)], now_ms=NOW))
            gate.set()
            assert [len(f.result(30)) for f in futs] == sizes
        assert set(shapes) <= warmed, set(shapes) - warmed
        assert {s for s, c in shapes if s[2] == hi and not c}  # groups ran
        assert {s for s, c in shapes if s[2] == lo and c}  # repeats' scans
        assert [fn._cache_size() for fn in programs] == compiled
    finally:
        comb.close()


@pytest.mark.parametrize("lo, hi", [(8192, 8192), (2048, 8192), (64, 8192)],
                         ids=["8192", "2048..8192", "64..8192"])
def test_every_group_shape_a_pull_launches_is_one_the_warm_up_compiled(
        monkeypatch, lo, hi):
    """The pull loop hands a pull's run of one-window chunks to the engine
    as scan groups (service/peerlink.py _columnar_run), and a group
    launches `max_width` wide whatever its windows' own bucket width is:
    the table-carried group shapes are the ones `warmup_pipeline` compiles,
    at the top width only. So on the benchmark's one-width ladder, on the
    hot cell's and on the shipped one (at a small table), after the two
    warm-ups a pull of 2 to 8 clean 1000-request frames compiles nothing:
    not under the engine lock, not anywhere."""
    from gubernator_tpu.types import RateLimitReq
    from gubernator_tpu.utils.platform import CompileWatch

    from test_columnar_pipeline import send_in_one_pull

    scan = 8  # the daemon's GUBER_PIPELINE_SCAN
    chunk_cap(monkeypatch, 1000)  # MAX_BATCH_SIZE, whatever the module pinned
    eng = Engine(capacity=2 * hi, min_width=lo, max_width=hi)
    if not eng.supports_columnar():
        pytest.skip("native columnar prep unavailable")
    eng.warmup()
    eng.warmup_pipeline(max_group=scan)
    shapes, real = [], eng._dispatch_scan_staged
    eng._dispatch_scan_staged = lambda stacked, now_ms, carried=False, \
        live=None, width=None: (
        shapes.append(((len(stacked), 9, width or stacked.shape[2]),
                       carried)),
        real(stacked, now_ms, carried, live, width))[1]
    instance, service, client = _serve(eng, workers=1, pipeline_depth=3,
                                       pipeline_scan=scan)
    watch = CompileWatch()
    try:
        for n_frames in range(2, 9):
            frames = [[RateLimitReq(name="gw", unique_key=f"k{i}", hits=1,
                                    limit=1000, duration=60_000)
                       for i in range(1000)] for _ in range(n_frames)]
            got = send_in_one_pull(service, client, frames)
            assert [len(out) for out in got] == [1000] * n_frames
            assert not any(r.error for out in got for r in out)
        assert watch.facts()["count"] == 0
        assert {s for s, _c in shapes} == {(k, 9, hi) for k in (2, 4, 8)}
        assert not any(c for _s, c in shapes)  # the table as the carry
        assert service.stats["columnar_windows"] == 2 + 3 + 4 + 4 + 6 + 7 + 8
    finally:
        watch.close()
        client.close()
        service.close()
        instance.close()


@pytest.mark.parametrize("lo, hi, programs, carried", [
    # the hot ladder: 3 widths x 3 staging formats, 15 carried scans at the
    # bottom, 9 group scans at the top (33 before the carry too)
    (2048, 8192, 33, 15),
    # one width: 3 + 15 table-carried scans, 9 of them the group shapes (18
    # before the carry too: one program a shape)
    (8192, 8192, 18, 0),
])
def test_the_programs_the_two_warm_ups_compile(lo, hi, programs, carried):
    """Counted by the launches the warm-ups make, nothing compiled: each
    distinct (program, argument shapes) is one XLA program."""
    eng = Engine(capacity=2 * hi, min_width=lo, max_width=hi)
    if not eng.supports_pipeline():
        pytest.skip("native prep unavailable")
    seen = set()

    def spy(name):
        def launch(state, *args):
            seen.add((name,) + tuple(np.shape(a) for a in args[:-1]))
            return state, None
        return launch

    eng._decide_packed = spy("packed_wide")
    eng._decide_packed_compact = spy("packed_compact")
    eng._decide_packed_lean = spy("packed_lean")
    eng._scans = tuple(spy("scan_" + f) for f in ("wide", "compact", "lean"))
    eng._scans_carried = tuple(
        spy("carry_" + f) for f in ("wide", "compact", "lean"))
    eng.warmup()
    eng.warmup_pipeline(max_group=8)  # the daemon's GUBER_PIPELINE_SCAN
    assert len(seen) == programs
    on_rows = {s for s in seen if s[0].startswith("carry_")}
    on_table = {s for s in seen if s[0].startswith("scan_")}
    widths = {w for w in (lo, 2 * lo, 4 * lo) if w <= hi}
    assert len(on_rows) == carried  # the rest: the single-window programs
    assert len(seen) - len(on_table) - carried == 3 * len(widths)
    assert {s[1][-1] for s in on_rows} <= {lo} - {hi}
    assert {s[1][-1] for s in on_table} == {hi}

"""Decision ledger & budget-conservation audit plane (obs/ledger.py).

Four layers, mirroring the subsystem's structure:

- unit: attribution folding, window rolls, the conservation rule
  (admits <= limit + minted + declared slack), the over-admission
  distribution, and the pending-ring / key-table backpressure counters;
- differential: the GUBER_LEDGER=0 escape hatch is bit-identical on the
  serving path — the SAME request stream through a ledger-on and a
  ledger-off instance produces byte-identical decisions, and the off
  node's counters stay all-zero (the hatch removes the plane, it does
  not merely silence it);
- interleavings (chaos-marked): lease grant -> owner circuit cut ->
  TTL fail-close converges to `owner remaining == limit - total admits`
  with the ledger agreeing hit-for-hit, and the reshard kill-mid-transfer
  amnesty never shows NEGATIVE over-admission (undershoot folds to zero,
  not below);
- drill: a test-only `mint` authority (zero slack by construction)
  over-admits one window, the audit flags it, the `over_admission`
  anomaly trips on the rising edge, and the captured bundle carries the
  causal spine (ledger.violation -> anomaly.over_admission).

The operator report (scripts/ledger_report.py) renders real endpoint
bodies offline — main() only adds the fetch.
"""

import dataclasses
import itertools
import json
import os
import time

import numpy as np
import pytest

from gubernator_tpu.cluster.harness import LocalCluster
from gubernator_tpu.cluster.harness import test_behaviors as _behaviors
from gubernator_tpu.models.engine import Engine
from gubernator_tpu.native import pack_keys, unpack_keys
from gubernator_tpu.obs.bundle import BundleWriter
from gubernator_tpu.obs.ledger import (
    AUTHORITIES,
    MINT_AUTHORITY,
    DecisionLedger,
    authority,
    current_authority,
    ledger_enabled_default,
)
from gubernator_tpu.service import faults
from gubernator_tpu.service.config import InstanceConfig
from gubernator_tpu.service.instance import Instance
from gubernator_tpu.service.leases import LEASED_METADATA_KEY
from gubernator_tpu.types import (
    Algorithm,
    PeerInfo,
    RateLimitReq,
    Status,
)


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.clear()


def _rl(key, hits=1, limit=1000, duration=3_600_000, behavior=0,
        name="led"):
    return RateLimitReq(name=name, unique_key=key, hits=hits, limit=limit,
                        duration=duration, behavior=behavior,
                        algorithm=Algorithm.TOKEN_BUCKET)


def _single(ledger_enabled=True, capacity=4096):
    """A self-owned single instance: every request serves locally, no RPC."""
    inst = Instance(InstanceConfig(backend=Engine(capacity=capacity),
                                   ledger_enabled=ledger_enabled),
                    advertise_address="127.0.0.1:1")
    inst.set_peers([PeerInfo(address="127.0.0.1:1")])
    return inst


# --------------------------------------------------------------------- unit


class TestConservationRule:
    def test_within_limit_no_violation(self):
        led = DecisionLedger(enabled=True)
        for _ in range(10):
            led.record_key("a", 1, int(Status.UNDER_LIMIT), 100, 5000)
        rep = led.audit(force=True)
        assert rep["violations"] == 0
        assert rep["windows_rolled"] == 1
        t = led.totals()
        assert t["admits"]["owner"] == 10
        assert t["attempted"] == 10
        assert t["rejected"] == 0

    def test_rejections_never_count_as_admits(self):
        led = DecisionLedger(enabled=True)
        led.record_key("a", 80, int(Status.UNDER_LIMIT), 100, 5000)
        led.record_key("a", 500, int(Status.OVER_LIMIT), 100, 5000)
        led.audit(force=True)
        t = led.totals()
        assert t["admits"]["owner"] == 80
        assert t["rejected"] == 500
        assert t["attempted"] == 580
        assert t["violations"] == 0  # rejected mass is not admitted mass

    def test_reset_advance_rolls_the_window(self):
        led = DecisionLedger(enabled=True)
        led.record_key("a", 5, int(Status.UNDER_LIMIT), 100, 5000)
        # the next reset is later: the previous window closed
        led.record_key("a", 7, int(Status.UNDER_LIMIT), 100, 9000)
        t = led.totals()
        assert t["windows_rolled"] == 1
        led.audit(force=True)  # force also rolls the still-open window
        assert led.totals()["windows_rolled"] == 2
        assert led.totals()["violations"] == 0

    def test_owner_overshoot_is_a_violation(self):
        seen = []
        led = DecisionLedger(enabled=True,
                             emit=lambda kind, **kw: seen.append((kind, kw)))
        led.record_key("svc_hot", 150, int(Status.UNDER_LIMIT), 100, 5000)
        rep = led.audit(force=True)
        assert rep["violations"] == 1
        t = led.totals()
        assert t["max_overshoot"] == 50
        assert t["overshoot_hits"] == 50
        assert [k for k, _ in seen] == ["ledger.violation"]
        assert seen[0][1]["key"] == "svc_hot"
        assert seen[0][1]["overshoot"] == 50
        v = rep["recent_violations"][-1]
        assert v["key"] == "svc_hot" and v["slack"] == 0

    def test_minted_budget_raises_the_bound(self):
        led = DecisionLedger(enabled=True)
        led.record_key("k", 100, int(Status.UNDER_LIMIT), 100, 5000,
                       auth="lease")
        led.record_minted("k", 60)
        led.record_key("k", 50, int(Status.UNDER_LIMIT), 100, 5000,
                       auth="lease")
        rep = led.audit(force=True)
        # 150 admits <= limit 100 + minted 60: paid-for budget, no mint
        assert rep["violations"] == 0
        assert led.totals()["minted_budget"] == 60

    def test_slack_authority_declares_one_window(self):
        led = DecisionLedger(enabled=True)
        led.record_key("k", 150, int(Status.UNDER_LIMIT), 100, 5000,
                       auth="degraded")
        assert led.audit(force=True)["violations"] == 0  # 50 <= slack 100
        led.record_key("k2", 250, int(Status.UNDER_LIMIT), 100, 5000,
                       auth="degraded")
        assert led.audit(force=True)["violations"] == 1  # 150 > slack 100
        t = led.totals()
        assert t["overshoot_hits"] == 50 + 150  # both folded into the dist

    def test_unexercised_slack_contributes_nothing(self):
        led = DecisionLedger(enabled=True)
        # all owner-authority: the degraded/reshard slack never applies
        led.record_key("k", 101, int(Status.UNDER_LIMIT), 100, 5000)
        assert led.audit(force=True)["violations"] == 1

    def test_overshoot_distribution_quantiles(self):
        led = DecisionLedger(enabled=True)
        led.record_key("k", 150, int(Status.UNDER_LIMIT), 100, 5000,
                       auth=MINT_AUTHORITY)
        led.audit(force=True)
        over = led.endpoint_body()["overshoot"]
        assert over["n"] == 1
        assert over["max_hits"] == 50
        # log2 buckets: 50 lands in the 2^6 bucket
        assert over["p50_hits"] == 64 and over["p99_hits"] == 64

    def test_key_capacity_declines_new_buckets(self):
        led = DecisionLedger(enabled=True, key_capacity=2)
        for i in range(4):
            led.record_key(f"k{i}", 1, int(Status.UNDER_LIMIT), 100, 5000)
        t = led.totals()
        assert t["keys_tracked"] == 2
        assert t["key_overflow"] == 2

    def test_pending_ring_drops_at_cap(self):
        led = DecisionLedger(enabled=True, pending_cap=1)
        led.note_arrays([1], [5], [0], [100], [5000])
        led.note_arrays([2], [5], [0], [100], [5000])
        t = led.totals()
        assert t["pending_windows"] == 1
        assert t["pending_dropped"] == 1

    def test_audit_resolves_slots_through_the_directory(self):
        led = DecisionLedger(enabled=True)
        led.note_arrays([3, 7, -1], [10, 4, 9], [0, 1, 0],
                        [100, 50, 1], [5000, 5000, 1])

        class _Dir(_FakeEngine):
            def resolve_slots(self, want):
                assert -1 not in want
                return super().resolve_slots(want)

        led.audit(engine=_Dir({3: "alpha"}), force=True)  # 7 fell out
        t = led.totals()
        assert t["admits"]["owner"] == 10
        assert t["rejected"] == 0  # slot 7's rejection went unattributed too
        assert t["unattributed_hits"] == 4
        assert t["pending_windows"] == 0

    def test_maybe_audit_is_rate_limited(self):
        led = DecisionLedger(enabled=True, audit_min_interval_s=60.0)
        assert led.maybe_audit() is True
        assert led.maybe_audit() is False  # inside the min interval
        assert led.totals()["audits"] == 1

    def test_authority_scope_nests_and_resets(self):
        assert current_authority() == "owner"
        with authority("degraded"):
            assert current_authority() == "degraded"
            with authority("reshard"):
                assert current_authority() == "reshard"
            assert current_authority() == "degraded"
        assert current_authority() == "owner"

    def test_env_hatch_parses_go_bool(self, monkeypatch):
        monkeypatch.setenv("GUBER_LEDGER", "false")
        assert ledger_enabled_default() is False
        assert DecisionLedger().enabled is False
        monkeypatch.setenv("GUBER_LEDGER", "1")
        assert ledger_enabled_default() is True
        monkeypatch.delenv("GUBER_LEDGER")
        assert ledger_enabled_default() is True  # default ON

    def test_env_hatch_reaches_daemon_config(self, monkeypatch):
        from gubernator_tpu.cmd.envconf import config_from_env
        monkeypatch.setenv("GUBER_LEDGER", "0")
        assert config_from_env([]).ledger_enabled is False
        monkeypatch.setenv("GUBER_LEDGER", "on")
        assert config_from_env([]).ledger_enabled is True


# ------------------------------------------------------------- differential


class _PerLaneLedger(DecisionLedger):
    """The reference fold: the audit as it was before the grouped fold —
    every drained lane walked in Python, its slot named through a dict,
    its key sent to _record_locked. The program keeps only the grouped
    fold (obs/ledger.py _fold_lanes_locked); this one says what it has to
    equal."""

    def _audit(self, engine, now_ms, force):
        self._last_audit = time.monotonic()
        if now_ms is None:
            now_ms = int(time.time() * 1000)
        with self._pending_lock:
            pending, self._pending = self._pending, []
        resolved = {}
        if pending and engine is not None:
            want = set()
            for sh, _resp, _auth in pending:
                want.update(int(s) for s in sh[0].tolist())
            want.discard(-1)
            try:
                resolved = engine.resolve_slots(want)
            except Exception:  # noqa: BLE001 — audit never raises
                resolved = {}
        with self._lock:
            for sh, resp, auth in pending:
                sl = sh[0].tolist()
                hl = sh[1].tolist()
                stl = resp[0].tolist()
                ll = resp[1].tolist()
                rl = resp[3].tolist()
                for j, s in enumerate(sl):
                    if s < 0:
                        continue  # padding lane, not a lost key
                    key = resolved.get(int(s))
                    if key is None:
                        self._unattributed += hl[j]
                        continue
                    self._record_locked(key, hl[j], stl[j], ll[j],
                                        rl[j], auth)
            for key, b in list(self._buckets.items()):
                if b.window and (force or b.window <= now_ms):
                    self._roll_locked(key, b)
            self._audits += 1
            report = self._report_locked()
        if engine is not None:
            self._ground_truth_check(engine)
            with self._lock:
                report["ground_truth"] = dict(self._ground_truth)
        return report


class _FakeEngine:
    """A directory as the ledger sees one: slot <-> key, one to one (some
    slots hold no key), changed by hand between audits, and device
    counters a fixed amount above the ledger's. `named` keeps every slot
    whose name was asked for."""

    def __init__(self, names):
        assert len(set(names.values())) == len(names)
        self.names = names
        self.named = []

    def resolve_slots(self, want):
        want = [int(s) for s in want]
        self.named.extend(want)
        return {s: self.names[s] for s in want if s in self.names}

    def peek_slots(self, keys):
        if isinstance(keys, tuple):
            keys = unpack_keys(*keys)
        slot_of = {k: s for s, k in self.names.items()}
        return np.asarray([slot_of.get(k, -1) for k in keys], np.int64)

    def slots_live(self, slots):
        return np.asarray([int(s) in self.names for s in slots], bool)

    def device_hit_counts(self, keys):
        return {k: 7 * len(k) for k in keys if not k.endswith("3")}


_NEW_COUNTERS = ("slots_asked", "slots_resolved", "slots_named",
                 "lanes_folded")


def _ledger_state(led):
    """Everything the ledger serves or keeps, in comparable form."""
    body = led.endpoint_body()
    for name in _NEW_COUNTERS:
        body["totals"].pop(name)
    with led._lock:
        buckets = [(k, b.window, b.limit, dict(b.admits), b.attempted,
                    b.rejected, b.minted, b.lifetime_attempted)
                   for k, b in led._buckets.items()]
        hist = list(led._over_counts)
    return {"body": body, "debug": led.debug(), "buckets": buckets,
            "overshoot_counts": hist}


def _churn(rng, names, n_slots, fresh):
    """The directory between a decision and its audit: one change to the
    slot <-> key map, which stays one to one."""
    held = sorted(names)
    free = [s for s in range(n_slots) if s not in names]
    kind = rng.choice(["move", "recycle_new", "recycle_held", "drop"])
    if kind == "move" and held and free:
        # a key evicted and looked up again: it lives at another slot
        names[rng.choice(free)] = names.pop(rng.choice(held))
    elif kind == "recycle_new" and held:
        # a slot recycled to a key nobody has met
        names[rng.choice(held)] = f"fresh{next(fresh)}"
    elif kind == "recycle_held" and len(held) >= 2:
        # a slot recycled to a key that lived elsewhere: the slot's old
        # key is gone, the new one moved
        to, frm = rng.sample(held, 2)
        names[to] = names.pop(frm)
    elif kind == "drop" and held:
        del names[rng.choice(held)]  # a key the directory lost


class TestGroupedFoldEqualsPerLane:
    """The audit asks the directory where its tracked keys live and matches
    lanes against those slots; lanes of untracked keys are counted in
    numpy. What it leaves behind must be what the per-lane walk, which
    names every slot, leaves, to the order of the violation events."""

    @pytest.mark.parametrize("fold_lanes", [None, 20],
                             ids=["one_part", "parts_of_20_lanes"])
    @pytest.mark.parametrize("seed", range(12))
    def test_random_pending_sequences(self, seed, fold_lanes, monkeypatch):
        import random

        if fold_lanes:
            # an audit folds its lanes a bounded run of windows at a time
            # (a tick at 300k decisions/s is three parts): many parts an
            # audit must leave what one pass leaves
            monkeypatch.setattr(DecisionLedger, "_FOLD_LANES", fold_lanes)
        rng = random.Random(1000 + seed)
        n_slots = rng.choice([40, 120])
        # one key a slot, as in a directory; some slots hold none
        names = {s: f"key{s}" for s in range(n_slots)
                 if rng.random() >= 0.12}
        engine = _FakeEngine(names)
        fresh = itertools.count()
        capacity = rng.choice([4, 16, 1000])
        events = ([], [])
        leds = (
            DecisionLedger(enabled=True, key_capacity=capacity,
                           emit=lambda kind, **kw: events[0].append(
                               (kind, kw))),
            _PerLaneLedger(enabled=True, key_capacity=capacity,
                           emit=lambda kind, **kw: events[1].append(
                               (kind, kw))),
        )
        base = 1_700_000_000_000
        tick = 0
        for audit_no in range(4):
            for _ in range(rng.randrange(0, 7)):
                n = rng.randrange(1, 60)
                slots = [rng.choice([-1] + list(range(n_slots)) * 3)
                         for _ in range(n)]
                hits = [rng.randrange(0, 6) for _ in range(n)]
                status = [int(rng.random() < 0.3) for _ in range(n)]
                limit = [rng.choice([0, 3, 8, 50]) for _ in range(n)]
                if rng.random() < 0.4:
                    # a leaky bucket's reset moves with every decision:
                    # each lane closes the window the one before opened
                    tick += n
                    reset = [base + 1000 * audit_no + tick - n + j
                             for j in range(n)]
                else:
                    # resets step forward inside one audit (windows roll
                    # in the middle of it), fall back (late lanes), are 0
                    reset = [rng.choice([0, base + 1000 * rng.randrange(
                        audit_no, audit_no + 4)]) for _ in range(n)]
                auth = rng.choice(["owner", "owner", "degraded", "lease",
                                   MINT_AUTHORITY])
                for led in leds:
                    with authority(auth):
                        led.note_arrays(slots, hits, status, limit, reset)
            if rng.random() < 0.5:  # the per-key recorders in between
                direct = f"key{rng.randrange(n_slots)}"
                for led in leds:
                    led.record_key(direct, 2, 0, 8, base + 1000 * audit_no)
                    led.record_minted("key1", 3)
            # the directory moves on between the decisions and the audit:
            # tracked keys change slot, tracked slots change hands (to a
            # new key, to another tracked key), tracked keys are lost
            for _ in range(rng.randrange(0, 6) if audit_no else 0):
                _churn(rng, names, n_slots, fresh)
            now_ms = base + 1000 * audit_no + 500
            force = audit_no == 3
            reports = [led.audit(engine=engine, now_ms=now_ms, force=force)
                       for led in leds]
            assert reports[0] == reports[1]
            assert _ledger_state(leds[0]) == _ledger_state(leds[1])
            assert events[0] == events[1]
        t = leds[0].totals()
        assert t["audits"] == 4
        assert t["lanes_folded"] >= t["slots_asked"] >= t["slots_resolved"]
        # names are bought for newcomers while there is room, no others
        assert t["slots_named"] <= min(capacity, t["slots_resolved"])
        if capacity == 4:
            assert t["key_overflow"] > 0

    def test_the_cases_the_random_ones_must_have_met(self):
        """One sequence with every feature by construction: capacity
        crossed mid-window (room for some newcomers, not all), a roll and
        a violation inside one audit, two authorities, padding, a slot
        that holds no key; then, between audits, a tracked key moved to
        another slot, its old slot recycled to an untracked key, a tracked
        slot recycled to another tracked key, a tracked key gone."""
        seen = ([], [])
        leds = (DecisionLedger(enabled=True, key_capacity=2,
                               emit=lambda k, **kw: seen[0].append((k, kw))),
                _PerLaneLedger(enabled=True, key_capacity=2,
                               emit=lambda k, **kw: seen[1].append((k, kw))))
        # the reference names every slot it drains: it asks a twin
        engine, twin = (_FakeEngine({1: "a", 2: "b", 3: "c"})
                        for _ in range(2))

        def audit(**kw):
            twin.names = engine.names
            leds[0].audit(engine=engine, **kw)
            leds[1].audit(engine=twin, **kw)
            assert _ledger_state(leds[0]) == _ledger_state(leds[1])
            assert seen[0] == seen[1]
            return leds[0].totals()

        for led in leds:
            led.note_arrays([2, -1, 1, 3, 9], [1, 9, 4, 2, 5],
                            [0, 0, 0, 0, 0], [3, 3, 3, 3, 3],
                            [5000, 5000, 5000, 5000, 5000])
            with authority("degraded"):
                led.note_arrays([1, 3, 1, 2], [4, 1, 1, 1], [0, 0, 1, 0],
                                [3, 3, 3, 3], [5000, 5000, 9000, 9000])
        t = audit(now_ms=6000)
        assert t["keys_tracked"] == 2  # "b" then "a"; "c" found no room
        assert engine.named == [2, 1]  # and its name was never asked for
        assert t["key_overflow"] == 2  # both lanes of slot 3
        assert t["unattributed_hits"] == 5  # slot 9
        # "a": 4 owner + 4 degraded against limit 3 rolls at reset 9000
        # with overshoot 5 > one window of slack 3
        assert t["violations"] == 1 and t["windows_rolled"] == 2
        assert [kw["key"] for _k, kw in seen[0]] == ["a"]
        assert (t["slots_asked"], t["slots_resolved"], t["slots_named"],
                t["lanes_folded"]) == (4, 3, 2, 8)

        # "a" was evicted and came back at slot 5; slot 1 went to "d"
        engine.names = {5: "a", 1: "d", 2: "b", 3: "c"}
        for led in leds:
            led.note_arrays([1, 5, 2], [2, 1, 1], [0, 0, 0], [3, 3, 3],
                            [9000, 9000, 9000])
        t = audit(now_ms=9500)
        assert t["key_overflow"] == 3  # slot 1 is "d" now: untracked
        assert t["admits"]["owner"] == 1 + 4 + 1 + 1  # slot 5 is "a"
        assert t["windows_rolled"] == 4 and t["violations"] == 1
        assert (t["slots_asked"], t["slots_resolved"], t["slots_named"],
                t["lanes_folded"]) == (7, 6, 2, 11)

        # slot 5 went on to "b", which left slot 2 empty; "a" is gone
        engine.names = {5: "b", 1: "d", 3: "c"}
        for led in leds:
            led.note_arrays([5, 2], [2, 7], [0, 0], [3, 3], [12000, 12000])
        t = audit(force=True)
        assert t["admits"]["owner"] == 7 + 2  # slot 5 is "b"
        assert t["unattributed_hits"] == 5 + 7  # slot 2 holds no key
        assert t["windows_rolled"] == 5 and t["keys_tracked"] == 2
        assert (t["slots_asked"], t["slots_resolved"], t["slots_named"],
                t["lanes_folded"]) == (9, 7, 2, 13)
        assert engine.named == [2, 1]  # a full ledger buys no name

    def test_the_counters_say_what_an_audit_was_asked(self):
        led = DecisionLedger(enabled=True)
        led.note_arrays([3, 7, -1, 3], [1, 1, 1, 1], [0, 0, 0, 0],
                        [9, 9, 9, 9], [5000, 5000, 5000, 5000])
        led.audit()  # no engine: nothing is asked, every lane is folded
        t = led.totals()
        assert (t["slots_asked"], t["slots_resolved"],
                t["lanes_folded"], t["unattributed_hits"]) == (0, 0, 3, 3)
        led.note_arrays([3, 7], [1, 1], [0, 0], [9, 9], [5000, 5000])
        led.audit(engine=_FakeEngine({3: "alpha"}))
        t = led.totals()
        assert (t["slots_asked"], t["slots_resolved"],
                t["lanes_folded"], t["unattributed_hits"]) == (2, 1, 5, 4)

        class _Broken(_FakeEngine):
            def slots_live(self, slots):
                raise RuntimeError("directory gone")

        led.note_arrays([3], [2], [0], [9], [5000])
        led.audit(engine=_Broken({3: "alpha"}))  # the audit never raises
        t = led.totals()
        assert (t["slots_asked"], t["slots_resolved"],
                t["lanes_folded"], t["unattributed_hits"]) == (3, 1, 6, 6)


class TestNamesBoughtAreBoundedByTheRoomLeft:
    def test_a_full_ledger_buys_no_name_whatever_it_drains(self):
        """The bound, without a clock: 100,000 distinct live slots drained
        into a ledger that has room for 10 more keys cost 10 names, those
        of the first newcomers by arrival; drained into a full one, none."""
        n = 100_000
        engine = _FakeEngine({s: f"k{s}" for s in range(n)})
        led = DecisionLedger(enabled=True)
        cap = led.key_capacity
        assert cap == 8192  # what service/instance.py runs with
        for s in range(cap - 10):
            led.record_key(f"k{s}", 1, 0, 100, 5000)
        arrival = np.random.default_rng(7).permutation(n)

        def drain():
            for lo in range(0, n, 1000):
                part = arrival[lo:lo + 1000]
                led.note_arrays(part, np.ones(1000, int), np.zeros(1000, int),
                                np.full(1000, 100), np.full(1000, 5000))
            led.audit(engine=engine, now_ms=1000)
            return led.totals()

        t = drain()
        first = [s for s in arrival.tolist() if s >= cap - 10][:10]
        assert engine.named == first
        assert set(led._buckets) >= {f"k{s}" for s in first}
        assert (t["keys_tracked"], t["slots_named"], t["slots_asked"],
                t["slots_resolved"], t["lanes_folded"]) == (cap, 10, n, n, n)
        assert t["key_overflow"] == n - cap and t["unattributed_hits"] == 0

        t = drain()  # full: every drained slot is live, none is named
        assert engine.named == first
        assert (t["keys_tracked"], t["slots_named"], t["slots_asked"],
                t["slots_resolved"], t["lanes_folded"]) == (
                    cap, 10, 2 * n, 2 * n, 2 * n)
        assert t["key_overflow"] == 2 * (n - cap)
        assert t["attempted"] == (cap - 10) + 2 * cap


class TestAuditOnARealEngine:
    def test_an_evicting_directory_is_audited_as_the_per_lane_walk_does(self):
        """The differential on the shipped directory: 600 keys through 256
        slots, so between a window and its audit slots are recycled,
        tracked keys move and are lost; a ledger with room for 64 keys
        fills up on the way. Both ledgers drain the same windows and ask
        the same engine at the same instant."""
        eng = Engine(capacity=256, min_width=64, max_width=64)
        seen = ([], [])
        led = DecisionLedger(enabled=True, key_capacity=64,
                             emit=lambda k, **kw: seen[0].append((k, kw)))
        ref = _PerLaneLedger(enabled=True, key_capacity=64,
                             emit=lambda k, **kw: seen[1].append((k, kw)))
        eng.ledger = led
        import random

        rng = random.Random(34)
        for audit_no in range(5):
            for _ in range(4):
                picks = rng.sample(range(600), 40)
                eng.get_rate_limits([_rl(f"k{i}", hits=rng.randrange(1, 4),
                                         limit=rng.choice([2, 5, 1000]))
                                     for i in picks])
            with led._pending_lock:
                ref._pending = list(led._pending)
            now_ms = int(time.time() * 1000) + 3_600_000 * (audit_no == 4)
            reports = [l.audit(engine=eng, now_ms=now_ms) for l in (led, ref)]
            assert reports[0] == reports[1]
            assert _ledger_state(led) == _ledger_state(ref)
            assert seen[0] == seen[1]
        t = led.totals()
        assert t["keys_tracked"] == 64 == t["slots_named"]
        assert t["key_overflow"] > 0 and t["windows_rolled"] > 0
        assert t["unattributed_hits"] == 0  # a 40-key call evicts, then
        # its window's own keys hold every slot it drained
        assert eng.directory.evictions > 0


class TestResolveSlots:
    def test_native_directory_against_the_python_twin_on_one_engine(self):
        """Engine.resolve_slots picks index lookup or the walk by what the
        directory offers; both name the same keys for the same slots. The
        same for the ledger audit's two questions: where keys live
        (peek_slots, over a list or a packed arena) and which slots hold
        a key at all (slots_live)."""
        from gubernator_tpu.models.keyspace import KeyDirectory

        eng = Engine(capacity=256, min_width=64, max_width=64)
        if not hasattr(eng.directory, "keys_for_slots"):
            pytest.skip("native directory unavailable")
        for lo in range(0, 300, 50):  # 300 keys through 256 slots: evicts
            eng.get_rate_limits([_rl(f"k{i}-é") for i in range(lo, lo + 50)])
        eng.directory.drop("led_k299-é")
        native_dir = eng.directory
        live = dict((s, k) for k, s in native_dir.items())
        assert 200 < len(live) < 256
        asked = [-1, 0, 5, 5, 255, 256, 10_000] + list(range(0, 256, 3))
        by_index = eng.resolve_slots(asked)
        keys = ["led_k299-é", "never seen", "led_k0-é"] + sorted(live.values())
        where = {k: s for s, k in live.items()}

        def the_audits_questions():
            peeked = eng.peek_slots(keys)
            assert peeked.dtype == np.int64
            assert peeked.tolist() == [where.get(k, -1) for k in keys]
            assert eng.peek_slots(pack_keys(keys)).tolist() == peeked.tolist()
            assert eng.peek_slots([]).tolist() == []
            held = eng.slots_live(np.asarray(asked))
            assert held.dtype == bool
            assert held.tolist() == [s in live for s in asked]
            assert eng.slots_live(asked).tolist() == held.tolist()
            assert eng.slots_live([]).tolist() == []

        evictions = native_dir.evictions
        the_audits_questions()
        twin = KeyDirectory(256)
        twin._map.update(native_dir.items())
        eng.directory = twin
        try:
            by_walk = eng.resolve_slots(asked)
            assert eng.resolve_slots(np.asarray(asked)) == by_walk
            the_audits_questions()
        finally:
            eng.directory = native_dir
        # asking changed nothing: the same keys at the same slots
        assert dict((s, k) for k, s in native_dir.items()) == live
        assert native_dir.evictions == evictions
        assert by_index == by_walk
        assert by_index == {s: live[s] for s in set(asked) if s in live}
        assert eng.resolve_slots([]) == {} == eng.resolve_slots([-1, 256])
        assert eng.resolve_slots(range(256)) == live


class TestEscapeHatchDifferential:
    """GUBER_LEDGER=0 must remove the plane, not degrade the data path."""

    def test_decisions_bit_identical_ledger_on_vs_off(self):
        """Differential: the same stream through ledger-on and ledger-off
        instances yields bit-identical responses — status, remaining,
        limit and reset agree on every single answer — and the off
        node's ledger counters are ALL zero afterwards."""
        on, off = _single(ledger_enabled=True), _single(ledger_enabled=False)
        try:
            frames = [
                [_rl(f"k{j}", hits=1, limit=5) for j in range(16)]
                for _ in range(12)
            ]
            for frame in frames:
                ra = on.get_rate_limits(frame)
                rb = off.get_rate_limits(frame)
                for a, b in zip(ra, rb):
                    assert (a.status, a.limit, a.remaining, a.error) == \
                           (b.status, b.limit, b.remaining, b.error)
                    # reset encodes each instance's window birth time;
                    # the two instances booted milliseconds apart
                    assert abs(a.reset_time - b.reset_time) < 5_000
            # the stream crossed the limit: both rejected identically
            assert any(r.status == Status.OVER_LIMIT
                       for r in on.get_rate_limits(frames[0]))

            on.ledger.audit(on.backend, force=True)
            off.ledger.audit(off.backend, force=True)
            t_on, t_off = on.ledger.totals(), off.ledger.totals()
            assert t_on["attempted"] > 0
            assert t_on["admits"]["owner"] == 16 * 5  # 5 admits per key
            assert t_on["violations"] == 0
            # hatch off: every counter stayed zero
            assert t_off["attempted"] == 0
            assert t_off["rejected"] == 0
            assert sum(t_off["admits"].values()) == 0
            assert t_off["windows_rolled"] == 0
            assert t_off["pending_dropped"] == 0
        finally:
            on.close()
            off.close()

    def test_disabled_ledger_parks_nothing(self):
        inst = _single(ledger_enabled=False)
        try:
            for _ in range(5):
                inst.get_rate_limits([_rl(f"p{j}") for j in range(8)])
            assert inst.ledger.totals()["pending_windows"] == 0
        finally:
            inst.close()


# ------------------------------------------------------------ interleavings


def _arm_leases(cluster, rate=20.0, window=0.1, ttl=0.8, fraction=0.5):
    for ci in cluster.instances:
        b = ci.instance.conf.behaviors
        b.hot_leases = True
        b.hot_lease_rate = rate
        b.hot_lease_window_s = window
        b.hot_lease_ttl_s = ttl
        b.hot_lease_fraction = fraction
        ci.instance.leases.arm()


@pytest.mark.chaos
class TestLeaseBrownoutInterleaving:
    def test_grant_owner_cut_ttl_fail_close_conserves(self):
        """The nastiest lease interleaving: budget minted (granted), the
        owner browns out behind an open circuit, the lease dies at TTL
        fail-close, the drain lands late. After settling, the owner's
        window holds EXACTLY limit - total admits, and the ledger agrees:
        every admitted hit is attributed (forwards + drained at the
        owner, lease-authority locals at the holder), outstanding granted
        budget returns to zero, and no node reports a violation."""
        c = LocalCluster().start(2)
        try:
            _arm_leases(c, ttl=0.8)
            for ci in c.instances:
                ci.instance.conf.behaviors.circuit_threshold = 3
                ci.instance.conf.behaviors.circuit_open_s = 2.0
            req = _rl("cons", limit=10_000, name="lease")
            owner = c.owner_of(req.hash_key())
            nonowner = next(ci for ci in c.instances if ci is not owner)

            admitted = leased = 0
            for _ in range(150):
                r = nonowner.instance.get_rate_limits([req])[0]
                if not r.error and r.status == Status.UNDER_LIMIT:
                    admitted += 1
                if r.metadata.get(LEASED_METADATA_KEY):
                    leased += 1
                time.sleep(0.002)
            assert leased > 0, "lease never engaged"

            # cut the owner: renewal freezes, the lease dies at TTL and
            # serving fails closed (strict forwards fail fast)
            faults.install(f"peer={owner.address};action=error")
            deadline = time.monotonic() + 1.6
            while time.monotonic() < deadline:
                r = nonowner.instance.get_rate_limits([req])[0]
                if not r.error and r.status == Status.UNDER_LIMIT:
                    admitted += 1
                    if r.metadata.get(LEASED_METADATA_KEY):
                        leased += 1
                time.sleep(0.005)
            assert nonowner.instance.leases.held_count() == 0

            # partition heals: the queued drain lands, everything settles
            faults.clear()
            time.sleep(0.3)
            nonowner.instance.global_manager.flush()
            time.sleep(0.4)
            peek = dataclasses.replace(req, hits=0)
            final = owner.instance.get_rate_limits([peek])[0]

            # conservation, cross-checked through the ledgers: the
            # owner's ledger counts exactly what the device window
            # absorbed (forwards synchronously, leased locals via the
            # drain), so post-TTL the authoritative remaining is
            # limit - total admits AS THE LEDGER COUNTED THEM
            owner.instance.ledger.audit(owner.instance.backend, force=True)
            nonowner.instance.ledger.audit(nonowner.instance.backend,
                                           force=True)
            t_owner = owner.instance.ledger.totals()
            t_holder = nonowner.instance.ledger.totals()
            assert final.remaining == 10_000 - t_owner["admits"]["owner"]
            # fail-close means the device never absorbs MORE than the
            # clients were admitted — drain flushes that died against the
            # open circuit are LOST hits (reference global.go semantics),
            # never minted ones
            assert t_owner["admits"]["owner"] <= admitted
            assert t_owner["admits"]["owner"] >= admitted - leased
            assert t_holder["admits"]["lease"] == leased
            # the holder spent only installed budget, never minted its own
            assert t_holder["minted_budget"] >= leased
            assert t_owner["violations"] == 0
            assert t_holder["violations"] == 0
            # satellite: the outstanding-budget gauge source drains to 0
            # once every grant expired (TTL long gone by now)
            assert owner.instance.leases.outstanding() == 0
        finally:
            faults.clear()
            c.stop()


@pytest.mark.chaos
class TestReshardAmnestyInterleaving:
    def test_kill_mid_transfer_amnesty_never_negative(self):
        """Exporter frames die after `begin`; the importer's transfer
        lease expires and the moved keys restart fresh (amnesty). The
        ledger's over-admission is a max(0, ·) fold: amnesty UNDERSHOOT
        (a window re-opened with spent budget forgotten) must never
        surface as negative over-admission, and amnesty itself must not
        read as minting."""
        behaviors = dataclasses.replace(
            _behaviors(), reshard=True, reshard_ttl_s=1.0,
            reshard_grace_s=0.3)
        cluster = LocalCluster().start(2, behaviors=behaviors)
        try:
            reqs = [_rl(f"amn-{i:03d}", hits=1, limit=100_000, name="amn")
                    for i in range(120)]
            via = cluster.instances[0].instance
            for _ in range(3):
                via.get_rate_limits(reqs)
            # every reshard frame after the begin ack drops
            faults.install("transport=reshard;calls=2-;action=error")
            cluster.start_instance(behaviors=behaviors)
            cluster.sync_peers()
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                busy = any(
                    ci.instance.reshard.debug()["planning"]
                    or any(s["state"] in ("streaming", "begin", "commit")
                           for s in ci.instance.reshard.debug()["sessions"])
                    for ci in cluster.instances)
                if not busy:
                    break
                time.sleep(0.25)
            faults.clear()
            # traffic resumes across the healed topology: amnesty keys
            # restart fresh on their new owner
            for _ in range(3):
                via.get_rate_limits(reqs)

            for ci in cluster.instances:
                rep = ci.instance.ledger.audit(ci.instance.backend,
                                               force=True)
                t = ci.instance.ledger.totals()
                # no negative anywhere: the fold clamps undershoot at 0
                over = rep["overshoot"]
                assert over["n"] >= 0 and over["total_hits"] >= 0
                assert over["max_hits"] >= 0 and over["p99_hits"] >= 0
                assert all(v >= 0 for v in t["admits"].values())
                assert t["overshoot_hits"] >= 0
                # amnesty is forgetting, not minting: nothing to overshoot
                # with per-key traffic far below the limit
                assert t["violations"] == 0
                assert sum(t["admits"].values()) <= t["attempted"]
        finally:
            faults.clear()
            cluster.stop()


# -------------------------------------------------------------------- drill


class TestMintDrill:
    def test_minted_budget_trips_over_admission_with_spine(self, tmp_path):
        """The deliberate-violation drill: a test-only `mint` authority
        (zero slack, not in the production taxonomy) over-admits one
        window. The audit flags it, the over_admission detector trips on
        the rising edge, and the captured bundle carries the full causal
        spine — ledger.violation then anomaly.over_admission — plus the
        ledger section naming the minting key."""
        cluster = LocalCluster().start(1)
        try:
            inst = cluster.instances[0].instance
            inst.bundle_writer = BundleWriter(str(tmp_path),
                                              min_interval_s=0.0)
            eng = inst.anomaly
            led = inst.ledger
            assert MINT_AUTHORITY not in AUTHORITIES
            t0 = time.monotonic() + 100.0
            eng.check(now=t0)  # quiet baseline sweep
            assert not eng.active["over_admission"]

            led.record_key("mint_drill", 150, int(Status.UNDER_LIMIT),
                           100, 5000, auth=MINT_AUTHORITY)
            led.audit(inst.backend, force=True)
            assert led.totals()["violations"] == 1
            assert led.totals()["admits_other"] == 150  # outside taxonomy

            eng.check(now=t0 + 5.0)
            assert eng.active["over_admission"]
            assert eng.trips["over_admission"] == 1
            assert "over_admission" in eng.health_note()
            assert inst.recorder.count("ledger.violation") == 1
            assert inst.recorder.count("anomaly.over_admission") == 1

            files = list(tmp_path.glob("bundle-*over_admission.json"))
            assert len(files) == 1
            bundle = json.loads(files[0].read_text())
            assert bundle["reason"] == "anomaly:over_admission"
            assert bundle["ledger"]["totals"]["violations"] == 1
            assert bundle["ledger"]["recent_violations"][-1]["key"] \
                == "mint_drill"
            kinds = [e["kind"] for e in bundle["flight_recorder"]]
            assert "ledger.violation" in kinds
            assert "anomaly.over_admission" in kinds
            # causality reads in order inside the spine
            assert kinds.index("ledger.violation") \
                < kinds.index("anomaly.over_admission")

            # steady violations -> falling edge clears the detector
            eng.check(now=t0 + 10.0)
            assert not eng.active["over_admission"]
        finally:
            cluster.stop()


# ------------------------------------------------------------------ surface


class TestLedgerSurfaces:
    def test_metric_families_exposed(self):
        cluster = LocalCluster().start(1)
        try:
            ci = cluster.instances[0]
            ci.instance.get_rate_limits(
                [_rl(f"m{i}", hits=2) for i in range(8)])
            ci.instance.ledger.audit(ci.instance.backend, force=True)
            text = ci.metrics.render(ci.instance).decode()
            for family in (
                "ledger_admits_total",
                "ledger_attempted_hits_total",
                "ledger_rejected_hits_total",
                "ledger_minted_budget_total",
                "ledger_windows_audited_total",
                "ledger_violations_total",
                "ledger_overshoot_hits_total",
                "ledger_keys_tracked",
                "lease_outstanding_budget",
            ):
                assert family in text, family
            line = next(
                ln for ln in text.splitlines()
                if ln.startswith('ledger_admits_total{authority="owner"}'))
            assert float(line.split()[1]) == 16.0
        finally:
            cluster.stop()


class TestLedgerReport:
    """scripts/ledger_report.py renders endpoint bodies offline."""

    def _import(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "ledger_report",
            os.path.join(os.path.dirname(__file__), os.pardir,
                         "scripts", "ledger_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _body(self, violate=False):
        led = DecisionLedger(enabled=True)
        led.record_key("svc_a", 40, int(Status.UNDER_LIMIT), 100, 5000)
        led.record_key("svc_b", 10, int(Status.UNDER_LIMIT), 100, 5000,
                       auth="lease")
        led.record_key("svc_b", 25, int(Status.OVER_LIMIT), 100, 5000)
        led.record_minted("svc_b", 30)
        if violate:
            led.record_key("svc_bad", 300, int(Status.UNDER_LIMIT),
                           100, 5000, auth=MINT_AUTHORITY)
        led.audit(force=True)
        return led.endpoint_body()

    def test_renders_held_invariant(self):
        lr = self._import()
        out = lr.render_report(self._body())
        assert "INVARIANT HELD" in out
        assert "owner" in out and "lease" in out
        assert "minted budget    30" in out
        assert "BUDGET MINTED" not in out

    def test_renders_minted_verdict_with_culprit(self):
        lr = self._import()
        out = lr.render_report(self._body(violate=True))
        assert "BUDGET MINTED" in out
        assert "svc_bad" in out
        assert "overshoot" in out

    def test_renders_disabled_and_empty_bodies(self):
        lr = self._import()
        led = DecisionLedger(enabled=False)
        out = lr.render_report(led.endpoint_body())
        assert "DISABLED" in out
        assert "no decisions observed yet" in out

    def test_main_reads_bundle_file_offline(self, tmp_path, capsys):
        lr = self._import()
        wrapped = tmp_path / "bundle.json"
        wrapped.write_text(json.dumps({"ledger": self._body(violate=True)}))
        assert lr.main(["ledger_report.py", "--file", str(wrapped)]) == 0
        assert "BUDGET MINTED" in capsys.readouterr().out
        assert lr.main(["ledger_report.py", "--file",
                        str(tmp_path / "missing.json")]) == 1

"""Decision ledger: live audit of the "budget is never minted" invariant.

Every authority-delegating subsystem promises the same conservation
property in prose — hot-key leases carve slices out of the owner's
remaining budget, degraded-local serving admits against a local copy,
reshard double-writes during the transfer window, GLOBAL answers from a
local cache — and each bounds its worst-case over-admission by
construction. Nothing measured whether the promise holds under real
traffic. This module is the instrument: every admitted hit is
attributed at decision time to its **source of authority**, and an
off-serving-path auditor checks, per key-window,

    Σ admits across authorities ≤ limit
                                 + minted lease budget
                                 + declared degraded/reshard/global slack

rendering measured over-admission as a distribution (and a violation
counter the `over_admission` anomaly detector gates on), not a hope.

Hot-path contract (the PhaseHist rule from obs/profile.py): the engine's
window paths pay O(1) per *window*, not per lane — each dispatch parks a
handful of small numpy column copies (slot, hits, status, limit, reset)
on a pending ring under a leaf lock. Attribution, bucket folding, window
rolling, and the conservation evaluation all run in `audit()`, off the
serving path — riding the cartographer harvest / anomaly ticker cadence.
An audit asks the directory where the keys it tracks live (key → slot,
one batch peek) and matches the drained lanes against those slots; of
every other drained slot it asks only whether a key holds it. What it
does per key is bounded by `key_capacity`, whatever a tick drained and
whatever the directory holds; what is per lane is numpy. A slot's name
(slot → hash-key) is bought only for a newcomer that can still get a
bucket.
Lone native decisions and the non-engine authorities (lease consume,
GLOBAL cache, minted budget) record per key directly: they are already
per-item paths.

Authorities:

- ``owner``        — decided against this node's authoritative window
                     (the device table row), including drained lease /
                     GLOBAL hits applied at the owner;
- ``lease``        — served from a locally-held lease slice
                     (service/leases.py try_consume), bounded by the
                     minted budget the owner attached to the grant;
- ``degraded``     — degraded-local fallback while the owner is
                     unreachable (availability over strictness; slack is
                     one window of `limit` per node by construction);
- ``reshard``      — admitted inside a reshard transfer window
                     (double-write / fresh-serve amnesty paths);
- ``global_cache`` — answered from the GLOBAL behavior's local cache
                     ahead of async reconciliation.

The test-only ``mint`` authority has **zero** declared slack: recording
through it manufactures budget from nowhere, which is exactly what the
deliberate-violation drill uses to prove the detector fires.

`GUBER_LEDGER=0` turns every observation site into a single attribute
test; the off path is bit-identical (differential-tested) because the
ledger only ever *reads* the staging/response columns.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import os
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from gubernator_tpu.native import pack_keys
from gubernator_tpu.obs import witness
from gubernator_tpu.obs.profile import background_of

# v2: totals carry what the audit's attribution pass was asked
# (slots_asked, slots_resolved, lanes_folded — cumulative).
# v3: slots_named beside them (names the audit bought from the directory).
LEDGER_SCHEMA_VERSION = 3

# Attribution taxonomy (docs/observability.md "## Decision ledger" pins
# it; renaming an authority is a schema_version bump, not a drift).
AUTHORITIES = ("owner", "lease", "degraded", "reshard", "global_cache")

# Deliberate-violation drill only: admits with no declared slack.
MINT_AUTHORITY = "mint"

# Authorities whose admissions are covered by a declared slack of one
# window of `limit` each (the documented worst case per subsystem:
# leases.py:29 / reshard.py amnesty / GLOBAL staleness bound).
_SLACK_AUTHORITIES = ("degraded", "reshard", "global_cache")

# log2 over-admission histogram: bucket i holds overshoots <= 2^i hits.
_NBUCKETS = 28

# a drained slot's code, below the tracked keys' indices
_UNRESOLVED = -1  # the directory holds no key at the slot
_UNTRACKED = -2  # a live slot whose key has no bucket and no room for one

_AUTHORITY: contextvars.ContextVar = contextvars.ContextVar(
    "guber_ledger_authority", default="owner")


def ledger_enabled_default() -> bool:
    """GUBER_LEDGER escape hatch (Go ParseBool values; default on — the
    conservation meter is the always-on invariant check, opting OUT is
    the deliberate act)."""
    raw = os.environ.get("GUBER_LEDGER", "").strip().lower()
    if raw in ("0", "f", "false", "no", "off"):
        return False
    return True


@contextlib.contextmanager
def authority(name: str):
    """Scope every decision recorded inside to `name` — the serving path
    declares its source of authority (degraded-local wraps its engine
    apply, the reshard amnesty path wraps its local apply) and the
    engine hooks pick it up without any new plumbing through the call
    stack."""
    token = _AUTHORITY.set(name)
    try:
        yield
    finally:
        _AUTHORITY.reset(token)


def current_authority() -> str:
    return _AUTHORITY.get()


def _among(sorted_slots, slots):
    """Where each of `slots` would sit in `sorted_slots` (not empty), and
    whether it is there."""
    at = np.minimum(np.searchsorted(sorted_slots, slots),
                    len(sorted_slots) - 1)
    return at, sorted_slots[at] == slots


class _Bucket:
    """Per-key conservation state: the open window plus key-lifetime
    attribution totals (lifetime survives window rolls so the auditor
    can hold it against the device row's col-7 attempted counter)."""

    __slots__ = ("window", "limit", "admits", "attempted", "rejected",
                 "minted", "lifetime_attempted")

    def __init__(self):
        self.window = 0  # reset_time ms identifying the open window
        self.limit = 0
        self.admits: Dict[str, int] = {}
        self.attempted = 0
        self.rejected = 0
        self.minted = 0
        self.lifetime_attempted = 0


class DecisionLedger:
    """Per-node decision ledger + conservation auditor."""

    def __init__(self, enabled: Optional[bool] = None,
                 key_capacity: int = 8192, pending_cap: int = 4096,
                 audit_min_interval_s: float = 2.0,
                 emit: Optional[Callable] = None):
        self.enabled = (ledger_enabled_default()
                        if enabled is None else bool(enabled))
        self.key_capacity = int(key_capacity)
        self.pending_cap = int(pending_cap)
        self.audit_min_interval_s = float(audit_min_interval_s)
        # flight-recorder hook (Instance wires recorder.emit); None keeps
        # the ledger standalone in engine-only tests
        self._emit = emit
        # hot path: window column copies park here — leaf lock, O(1) hold
        self._pending_lock = witness.make_lock("ledger.pending")
        self._pending: List[tuple] = []
        # off-path state: key buckets, distribution, counters
        self._lock = witness.make_lock("ledger.buckets")
        self._buckets: Dict[str, _Bucket] = {}
        # the buckets as the audit asks for them: (key, bucket) in the
        # order they were made (buckets are never removed, so a key's
        # index is for life), and their keys packed for the directory's
        # batch peek. Both trail _buckets until _tracked_arena_locked.
        self._tracked: List[tuple] = []
        self._arena: Optional[tuple] = None
        self._admits_total: Dict[str, int] = {}
        self._attempted_total = 0
        self._rejected_total = 0
        self._minted_total = 0
        self._windows_rolled = 0
        self._violations = 0
        self._overshoot_hits = 0
        self._max_overshoot = 0
        self._over_counts = [0] * _NBUCKETS
        self._over_n = 0
        self._overflow = 0  # key-capacity evictions declined
        self._pending_dropped = 0  # windows dropped at the ring cap
        self._unattributed = 0  # hits on slots the directory lost
        # what the audit's attribution pass was asked to do (cumulative;
        # the diff between two reads is the audits in between)
        self._slots_asked = 0  # distinct slots drained beside an engine
        self._slots_resolved = 0  # of those, slots that hold a key
        self._slots_named = 0  # of those, slots whose key's name was bought
        self._lanes_folded = 0  # decision lanes drained from the ring
        self._audits = 0
        self._last_audit = 0.0
        self._ground_truth = {"keys_checked": 0, "ledger_hits": 0,
                              "device_hits": 0, "breaches": 0}
        self._recent: List[dict] = []  # last few violation evaluations

    # ------------------------------------------------------------ hot path

    def note_slots(self, packed: np.ndarray, out: np.ndarray,
                   n0: int) -> None:
        """Park one dispatched window's attribution columns: slots+hits
        from the staged wide buffer, status/limit/reset from the response
        rows. O(1) per window — two small block copies and a list
        append; resolution and folding happen in audit()."""
        if not n0:
            return
        # two block copies: slot|hits are adjacent staging rows, the
        # response is one 4-row block — a handful of ns each, vs ~µs for
        # five per-row copies (the parking IS the hot-path cost)
        rec = (packed[:2, :n0].copy(), out[:4, :n0].copy(),
               _AUTHORITY.get())
        with self._pending_lock:
            if len(self._pending) >= self.pending_cap:
                self._pending_dropped += 1
                return
            self._pending.append(rec)

    def note_arrays(self, slots, hits, status, limit, reset) -> None:
        """Generic per-array entry (tests, non-engine batch recorders):
        builds the same (slots+hits, response-rows) record the engine
        block paths park."""
        n = len(slots)
        sh = np.empty((2, n), np.int64)
        sh[0] = slots
        sh[1] = hits
        resp = np.zeros((4, n), np.int64)
        resp[0] = status
        resp[1] = limit
        resp[3] = reset
        rec = (sh, resp, _AUTHORITY.get())
        with self._pending_lock:
            if len(self._pending) >= self.pending_cap:
                self._pending_dropped += 1
                return
            self._pending.append(rec)

    def stash_columns(self, packed: np.ndarray, n0: int):
        """Copy the slot/hits columns of a window whose readback is
        deferred (pipelined launch/collect, columnar submit/complete) —
        the staging buffer may be refilled before the collect runs, so
        the launch side parks copies and the collect side pairs them
        with the response rows via note_slots_deferred."""
        if not n0:
            return None
        return (packed[:2, :n0].copy(), _AUTHORITY.get())

    def note_slots_deferred(self, stash, rows: np.ndarray,
                            n0: int) -> None:
        if stash is None or not n0:
            return
        slots_hits, auth = stash
        rec = (slots_hits, rows[:4, :n0].copy(), auth)
        with self._pending_lock:
            if len(self._pending) >= self.pending_cap:
                self._pending_dropped += 1
                return
            self._pending.append(rec)

    # -------------------------------------------------- per-key recording

    def record_key(self, key: str, hits: int, status: int, limit: int,
                   reset: int, auth: Optional[str] = None) -> None:
        """Attribute one decision by key — the native lone-request path
        and every non-engine authority (lease consume, GLOBAL cache,
        degraded singles) record here directly."""
        if auth is None:
            auth = _AUTHORITY.get()
        with self._lock:
            self._record_locked(key, int(hits), int(status), int(limit),
                                int(reset), auth)

    def record_minted(self, key: str, budget: int) -> None:
        """A lease slice was installed for local consumption: `budget`
        hits of the owner's window are now legitimately spendable here.
        Grows the key's conservation bound for the open window."""
        if budget <= 0:
            return
        with self._lock:
            b = self._bucket_locked(key)
            if b is not None:
                b.minted += int(budget)
            self._minted_total += int(budget)

    # ------------------------------------------------------------ folding

    def _bucket_locked(self, key: str) -> Optional[_Bucket]:
        b = self._buckets.get(key)
        if b is None:
            if len(self._buckets) >= self.key_capacity:
                self._overflow += 1
                return None
            b = _Bucket()
            self._buckets[key] = b
        return b

    def _record_locked(self, key, hits, status, limit, reset, auth):
        b = self._bucket_locked(key)
        if b is None:
            return
        if reset and b.window and reset > b.window:
            self._roll_locked(key, b)
        if reset and not b.window:
            b.window = reset
        if limit:
            b.limit = limit
        b.attempted += hits
        b.lifetime_attempted += hits
        self._attempted_total += hits
        if status == 1:
            b.rejected += hits
            self._rejected_total += hits
        else:
            b.admits[auth] = b.admits.get(auth, 0) + hits
            self._admits_total[auth] = self._admits_total.get(auth, 0) + hits

    def _roll_locked(self, key: str, b: _Bucket) -> None:
        """Finalize one key-window and open a fresh one (the lifetime
        attempted counter survives)."""
        self._publish_locked(self._close_locked(key, b))

    def _close_locked(self, key: str, b: _Bucket) -> Optional[dict]:
        """_roll_locked without the publishing: the violation record, if
        the window was one, is the caller's to publish in its turn."""
        ev = self._judge_locked(key, b.window, b.limit, b.minted, b.admits,
                                b.attempted)
        b.window = 0
        b.admits = {}
        b.attempted = 0
        b.rejected = 0
        b.minted = 0
        return ev

    def _judge_locked(self, key: str, window: int, limit: int, minted: int,
                      admits: Dict[str, int],
                      attempted: int) -> Optional[dict]:
        """Evaluate conservation for one closed key-window and fold its
        overshoot into the distribution; returns the violation record when
        the window admitted more than its bound and declared slack."""
        total_admits = sum(admits.values())
        if not (total_admits or attempted):
            return None
        bound = limit + minted
        # each exercised slack authority declares one window of `limit`
        # as its documented worst case; an authority that admitted
        # nothing this window contributes no slack
        slack = limit * sum(1 for a in _SLACK_AUTHORITIES
                            if admits.get(a, 0))
        overshoot = max(0, total_admits - bound)
        self._windows_rolled += 1
        if overshoot:
            self._overshoot_hits += overshoot
            if overshoot > self._max_overshoot:
                self._max_overshoot = overshoot
            idx = min(overshoot.bit_length(), _NBUCKETS - 1)
            self._over_counts[idx] += 1
            self._over_n += 1
        if overshoot <= slack:
            return None
        self._violations += 1
        return {"key": key, "window": window, "limit": limit,
                "admits": dict(admits), "minted": minted,
                "overshoot": overshoot, "slack": slack}

    def _publish_locked(self, ev: Optional[dict]) -> None:
        if ev is None:
            return
        self._recent.append(ev)
        del self._recent[:-16]
        if self._emit is not None:
            try:
                self._emit("ledger.violation", key=ev["key"],
                           overshoot=ev["overshoot"], slack=ev["slack"],
                           limit=ev["limit"], minted=ev["minted"],
                           authorities=",".join(sorted(ev["admits"])))
            except Exception:  # noqa: BLE001 — audit never raises
                pass

    # ------------------------------------------------------------ auditing

    def maybe_audit(self, engine=None, now_ms: Optional[int] = None) -> bool:
        """Rate-limited audit for tickers (the anomaly engine calls this
        every check): no-op inside the min interval."""
        now = time.monotonic()
        if now - self._last_audit < self.audit_min_interval_s:
            return False
        self.audit(engine, now_ms=now_ms)
        return True

    def audit(self, engine=None, now_ms: Optional[int] = None,
              force: bool = False) -> dict:
        """The off-serving-path conservation pass: drain the pending
        window ring, resolve slots to keys through the engine directory,
        fold into key buckets, roll every window the clock has closed
        (all of them under `force` — the scenario sweep wants the final
        open windows judged too), and hold a sample of keys against the
        device table's lifetime col-7 attempted counters as ground
        truth. Returns the audit report also served by endpoint_body."""
        with background_of(engine, "ledger.audit"):
            return self._audit(engine, now_ms, force)

    # Lanes handled at a time. The lane columns of a fold and their
    # transients (a dozen arrays a lane) are sized by this, not by what a
    # tick drained: at 300k decisions/s a tick drains 1.5M lanes, and one
    # set of columns over all of them held ~200 MB at once, in arrays glibc
    # serves from the heap and keeps (the daemon's resident set +460 MB
    # against the same daemon at 108k; PERF.md PR 29). Half a million lanes
    # is a whole tick at 100k decisions/s. What is asked of the directory
    # (where the tracked keys live, which distinct slots hold a key) is
    # still asked once a tick.
    _FOLD_LANES = 1 << 19

    def _parts(self, pending):
        """The drained ring in arrival order, _FOLD_LANES lanes at a time."""
        part: List[tuple] = []
        lanes = 0
        for rec in pending:
            part.append(rec)
            lanes += rec[0].shape[1]
            if lanes >= self._FOLD_LANES:
                yield part
                part, lanes = [], 0
        if part:
            yield part

    def _audit(self, engine, now_ms: Optional[int], force: bool) -> dict:
        self._last_audit = time.monotonic()
        if now_ms is None:
            now_ms = int(time.time() * 1000)
        with self._pending_lock:
            pending, self._pending = self._pending, []
        # buckets are never removed: no room now is no room at the fold
        has_room = len(self._buckets) < self.key_capacity
        uniq, first = self._pending_slots(pending, ranked=has_room)
        asked = None
        if len(uniq) and engine is not None:
            try:
                with background_of(engine, "ledger.resolve_slots"):
                    asked = self._ask_directory(engine, uniq, first)
            except Exception:  # noqa: BLE001 — audit never raises
                asked = None
            self._slots_asked += len(uniq)
        with self._lock:
            if len(uniq):
                where = self._attribute_locked(uniq, asked)
                for part in self._parts(pending):
                    self._fold_lanes_locked(part, *where)
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

    def _pending_slots(self, pending, ranked: bool):
        """The drained ring's distinct slots, sorted, and (`ranked`: only
        while a newcomer can still get a bucket) for each the arrival rank
        of its first lane among the lanes that carry a slot (padding lanes,
        slot -1, dropped). Both empty when no lane does. Found a part at a
        time and merged: np.unique with return_index sorts stably, so a
        slot's first part is the one kept."""
        uniqs, firsts, seen = [], [], 0
        for part in self._parts(pending):
            slots = np.concatenate([sh[0] for sh, _resp, _auth in part])
            slots = slots[slots >= 0]
            if ranked:
                u, f = np.unique(slots, return_index=True)
                firsts.append(f + seen)
            else:
                u = np.unique(slots)
            uniqs.append(u)
            seen += slots.size
        if not seen:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        if not ranked:
            return np.unique(np.concatenate(uniqs)), None
        uniq, at = np.unique(np.concatenate(uniqs), return_index=True)
        return uniq, np.concatenate(firsts)[at]

    def _tracked_arena_locked(self):
        """(packed keys of the buckets, how many) with `_tracked` caught up
        to `_buckets`: packed anew only when a bucket was made since."""
        n = len(self._buckets)
        if len(self._tracked) < n:
            self._tracked.extend(
                itertools.islice(self._buckets.items(), len(self._tracked),
                                 None))
        if self._arena is None or len(self._arena[1]) - 1 != n:
            self._arena = pack_keys([key for key, _b in self._tracked])
        return self._arena, n

    def _ask_directory(self, engine, uniq, first):
        """What an audit asks the directory, outside the buckets' lock: where
        the tracked keys live now (one batch peek, no recency effect),
        which of the drained slots hold a key at all, and, only while a
        newcomer can still get a bucket, the names of the first `room`
        live slots of untracked keys by arrival. The directory is a
        bijection, so a slot holds key k iff peek(k) is that slot: a slot
        recycled since its decision goes to whoever holds it now, a
        tracked key that moved is met at its new slot, one the directory
        dropped matches no lane (models/keyspace.py resolve_slots'
        contract, read from the keys' side). -> (codes aligned with
        `uniq`: a tracked key's index or _UNRESOLVED or _UNTRACKED;
        the candidates' positions in `uniq` by arrival; their names by
        slot)."""
        with self._lock:
            arena, n = self._tracked_arena_locked()
        code = np.where(engine.slots_live(uniq), _UNTRACKED, _UNRESOLVED)
        if n:
            # two keys can both read one slot only if it changed hands
            # between their peeks: the first bucket keeps it this tick
            slots, index = np.unique(engine.peek_slots(arena),
                                     return_index=True)
            at, drained = _among(uniq, slots)
            code[at[drained]] = index[drained]
        room = self.key_capacity - n
        if room <= 0:
            return code, (), {}
        newcomers = np.flatnonzero(code == _UNTRACKED)
        newcomers = newcomers[np.argsort(first[newcomers])][:room]
        names = engine.resolve_slots(uniq[newcomers]) if len(newcomers) \
            else {}
        return code, newcomers.tolist(), names

    def _attribute_locked(self, uniq, asked):
        """Give the newcomers their buckets, in the order their first lanes
        arrived and while there is room, and -> what a lane is matched
        against: (the slots of tracked keys, sorted; the index in `tracked`
        beside each; the slots that hold no key, sorted; tracked). A lane
        at neither kind of slot is a live key's with no bucket. A key that
        record_key tracked since the directory was asked is met at the
        next audit."""
        if asked is None:  # no engine, or its directory failed
            return (np.empty(0, np.int64), np.empty(0, np.int64), uniq,
                    self._tracked)
        code, newcomers, names = asked
        self._slots_named += len(newcomers)
        self._tracked_arena_locked()
        for i in newcomers:
            key = names.get(int(uniq[i]))
            if key is None:  # dropped since the directory called it live
                code[i] = _UNRESOLVED
            elif key in self._buckets:  # record_key tracked it meanwhile
                code[i] = next(at for at, (k, _b) in enumerate(self._tracked)
                               if k == key)
            elif len(self._buckets) < self.key_capacity:
                b = self._buckets[key] = _Bucket()
                code[i] = len(self._tracked)
                self._tracked.append((key, b))
        self._slots_resolved += int(np.count_nonzero(code != _UNRESOLVED))
        held = code >= 0
        return (uniq[held], code[held], uniq[code == _UNRESOLVED],
                self._tracked)

    def _fold_lanes_locked(self, part, held_slots, held_index, lost_slots,
                           tracked) -> None:
        """Fold one run of drained windows (arrival order) into the key
        buckets, leaving what the per-lane walk (every lane through
        _record_locked; tests/test_ledger.py keeps it as the reference)
        would leave. A lane is matched against the slots of the tracked
        keys (at most key_capacity of them, whatever the tick drained):
        lanes of keys that are not tracked (most of them, once
        key_capacity keys are) are counted in numpy, not visited; lanes of
        tracked keys go to _fold_tracked_locked."""
        slot_hits, resps, rec_auths = zip(*part)
        slots = np.concatenate([sh[0] for sh in slot_hits])
        live = np.flatnonzero(slots >= 0)
        if not live.size:
            return
        self._lanes_folded += live.size
        lane_slots = slots[live]
        lane_code = np.full(live.size, _UNTRACKED, np.int64)
        if len(lost_slots):
            _at, hit = _among(lost_slots, lane_slots)
            lane_code[hit] = _UNRESOLVED
        if len(held_slots):
            at, hit = _among(held_slots, lane_slots)
            lane_code[hit] = held_index[at[hit]]
        hits = np.concatenate([sh[1] for sh in slot_hits])[live]
        self._unattributed += int(hits[lane_code == _UNRESOLVED].sum())
        self._overflow += int(np.count_nonzero(lane_code == _UNTRACKED))
        keep = np.flatnonzero(lane_code >= 0)
        if not keep.size:
            return
        at = live[keep]
        resp = np.concatenate(resps, axis=1)
        auths = sorted(set(rec_auths))
        auth_of = np.repeat([auths.index(a) for a in rec_auths],
                            [sh.shape[1] for sh in slot_hits])
        self._fold_tracked_locked(
            tracked, lane_code[keep], hits[keep], resp[0, at], resp[1, at],
            resp[3, at], auth_of[at], auths)

    def _fold_tracked_locked(self, tracked, kid, hits, status, limit, reset,
                             auth_of, auths) -> None:
        """Apply the lanes of tracked keys (`kid` indexes `tracked`, lanes
        in arrival order) as _record_locked would one by one, in work per
        key and per window instead of per lane.

        Sorted by key (arrival order kept inside a key), a key's lanes fall
        into segments: a new one starts wherever a lane's reset is past the
        window open before it, which is where _record_locked rolls. The
        first segment continues the bucket's open window unless the key's
        very first lane already rolls it; each later one is a window of
        its own, closed by the next; the last stays open in the bucket.
        Sums per segment are exact int64 reductions (the width of the
        device's own counters). A window that
        admitted no more than its limit can only count as rolled, so those
        are counted in numpy and only the others are judged one by one;
        violations are published in the arrival order of the lanes that
        closed their windows, as the per-lane walk emits them."""
        n = len(kid)
        order = np.argsort(kid, kind="stable")
        kid, hits, status, limit, reset, auth_of = (
            a[order] for a in (kid, hits, status, limit, reset, auth_of))
        lane = np.arange(n)
        is_gstart = np.ones(n, bool)
        is_gstart[1:] = kid[1:] != kid[:-1]
        gstart = np.flatnonzero(is_gstart)
        n_groups = len(gstart)
        group = np.cumsum(is_gstart) - 1
        buckets = [tracked[i] for i in kid[gstart].tolist()]
        window0 = np.asarray([b.window for _key, b in buckets], np.int64)
        limit0 = np.asarray([b.limit for _key, b in buckets], np.int64)

        # the window open before each lane: the running maximum of the
        # bucket's own and the resets so far (0 is "none"; stamps are not
        # negative). Ranks stand in for the stamps so that group * n_ranks
        # + rank, whose running maximum never crosses a group, cannot
        # overflow.
        stamps, rank = np.unique(np.concatenate([window0, reset]),
                                 return_inverse=True)
        n_ranks = len(stamps)
        base = group * n_ranks
        run = np.maximum.accumulate(base + rank[n_groups:])
        before = base + rank[:n_groups][group]
        inner = ~is_gstart
        before[inner] = np.maximum(before[inner], run[:-1][inner[1:]])
        open_before = stamps[before - base]
        rolls = (reset != 0) & (open_before != 0) & (reset > open_before)

        # segments, and what each adds up to
        is_sstart = rolls | is_gstart
        sstart = np.flatnonzero(is_sstart)
        n_segs = len(sstart)
        send = np.append(sstart[1:], n) - 1  # last lane of each
        seg_group = group[sstart]
        rolled_in = rolls[sstart].tolist()
        admitted = status != 1
        attempted = np.add.reduceat(hits, sstart)
        rejected = np.add.reduceat(np.where(admitted, 0, hits), sstart)
        admits, present = [], []
        for a in range(len(auths)):
            mine = admitted & (auth_of == a)
            admits.append(np.add.reduceat(np.where(mine, hits, 0), sstart))
            present.append(np.add.reduceat(mine.astype(np.int64), sstart) > 0)
        total_admits = np.sum(admits, axis=0)
        # the limit in force at a segment's end: the last non-zero one so
        # far in the key's lanes, else the bucket's
        last_set = np.maximum.accumulate(np.where(limit != 0, lane, -1))
        at = last_set[send]
        limit_end = np.where(at >= gstart[seg_group], limit[at],
                             limit0[seg_group])
        # the window a segment belongs to: the stamp that rolled it in,
        # else the bucket's, else the first stamp it meets
        first_set = np.minimum.reduceat(np.where(reset != 0, lane, n), sstart)
        met = np.where(first_set <= send, reset[np.minimum(first_set, n - 1)],
                       0)
        seg_window = np.where(
            rolls[sstart], reset[sstart],
            np.where(window0[seg_group] != 0, window0[seg_group], met))

        self._attempted_total += int(hits.sum())
        self._rejected_total += int(rejected.sum())
        for a, name in enumerate(auths):
            if present[a].any():
                self._admits_total[name] = (
                    self._admits_total.get(name, 0) + int(admits[a].sum()))

        # closed windows that are whole segments: every one but a key's
        # last, if something rolled it in (else it is the bucket's own)
        closed = rolls[sstart]
        closed[:-1] &= seg_group[1:] == seg_group[:-1]
        closed[-1] = False
        counted = closed & ((total_admits != 0) | (attempted != 0))
        judged = counted & (total_admits > limit_end)
        self._windows_rolled += int(np.count_nonzero(counted & ~judged))

        admits_l = [a.tolist() for a in admits]
        present_l = [p.tolist() for p in present]

        def admits_of(s):
            return {name: adm[s] for name, adm, there in zip(
                auths, admits_l, present_l) if there[s]}

        attempted_l, rejected_l = attempted.tolist(), rejected.tolist()
        limit_l, window_l = limit_end.tolist(), seg_window.tolist()
        # arrival rank of each segment's first lane, the one that closed
        # the window before it
        closer = order[sstart].tolist()
        events = []
        for s in np.flatnonzero(judged).tolist():
            key = buckets[seg_group[s]][0]
            ev = self._judge_locked(key, window_l[s], limit_l[s], 0,
                                    admits_of(s), attempted_l[s])
            if ev is not None:
                events.append((closer[s + 1], ev))

        # the buckets: continue the open window, roll it where the lanes
        # say, leave the key's last window open
        seg_lo = np.searchsorted(seg_group, np.arange(n_groups)).tolist()
        seg_hi = seg_lo[1:] + [n_segs]
        lifetime = np.add.reduceat(hits, gstart).tolist()
        for g, (key, b) in enumerate(buckets):
            b.lifetime_attempted += lifetime[g]
            s, last = seg_lo[g], seg_hi[g] - 1
            if not rolled_in[s]:
                if not b.window:
                    b.window = window_l[s]
                b.limit = limit_l[s]
                b.attempted += attempted_l[s]
                b.rejected += rejected_l[s]
                for name, hits_in in admits_of(s).items():
                    b.admits[name] = b.admits.get(name, 0) + hits_in
                if s == last:
                    continue
                s += 1
            ev = self._close_locked(key, b)
            if ev is not None:
                events.append((closer[s], ev))
            b.window = window_l[last]
            b.limit = limit_l[last]
            b.attempted = attempted_l[last]
            b.rejected = rejected_l[last]
            b.admits = admits_of(last)
        for _at, ev in sorted(events, key=lambda e: e[0]):
            self._publish_locked(ev)

    def _ground_truth_check(self, engine, sample: int = 64) -> None:
        """Hold the ledger's per-key lifetime attempted totals against
        the device rows' col-7 counters. The device counter is the
        durable on-accelerator truth for owner-resident keys; a key the
        ledger saw MORE attempts for than the device row did (and the
        row was never recycled: device >= ledger holds across expiry
        only one way) is attribution the serving path manufactured."""
        with self._lock:
            keys = [k for k, b in self._buckets.items()
                    if b.lifetime_attempted > 0][:sample]
            ledger_hits = {k: self._buckets[k].lifetime_attempted
                           for k in keys}
        if not keys:
            return
        try:
            device = engine.device_hit_counts(keys)
        except Exception:  # noqa: BLE001 — audit never raises
            return
        checked = lh = dh = breaches = 0
        for k in keys:
            if k not in device:
                continue  # not owner-resident here (leased/remote key)
            checked += 1
            lh += ledger_hits[k]
            dh += device[k]
            if ledger_hits[k] > device[k]:
                breaches += 1
        with self._lock:
            g = self._ground_truth
            g["keys_checked"] += checked
            g["ledger_hits"] += lh
            g["device_hits"] += dh
            g["breaches"] += breaches

    # ------------------------------------------------------------ surfaces

    def totals(self) -> dict:
        with self._lock:
            return {
                "admits": {a: self._admits_total.get(a, 0)
                           for a in AUTHORITIES},
                "admits_other": sum(
                    v for a, v in self._admits_total.items()
                    if a not in AUTHORITIES),
                "attempted": self._attempted_total,
                "rejected": self._rejected_total,
                "minted_budget": self._minted_total,
                "windows_rolled": self._windows_rolled,
                "violations": self._violations,
                "overshoot_hits": self._overshoot_hits,
                "max_overshoot": self._max_overshoot,
                "keys_tracked": len(self._buckets),
                "key_overflow": self._overflow,
                "pending_windows": len(self._pending),
                "pending_dropped": self._pending_dropped,
                "unattributed_hits": self._unattributed,
                "audits": self._audits,
                "slots_asked": self._slots_asked,
                "slots_resolved": self._slots_resolved,
                "slots_named": self._slots_named,
                "lanes_folded": self._lanes_folded,
            }

    def _overshoot_locked(self) -> dict:
        out = {"n": self._over_n, "total_hits": self._overshoot_hits,
               "max_hits": self._max_overshoot, "p50_hits": 0,
               "p99_hits": 0}
        if self._over_n:
            for q, field in ((0.50, "p50_hits"), (0.99, "p99_hits")):
                want = q * self._over_n
                seen = 0
                for i, c in enumerate(self._over_counts):
                    seen += c
                    if seen >= want:
                        out[field] = 1 << i
                        break
        return out

    def _report_locked(self) -> dict:
        return {
            "windows_rolled": self._windows_rolled,
            "violations": self._violations,
            "overshoot": self._overshoot_locked(),
            "recent_violations": list(self._recent),
        }

    def debug(self) -> dict:
        """The compact /v1/debug/vars section."""
        t = self.totals()
        with self._lock:
            over = self._overshoot_locked()
        return {
            "enabled": self.enabled,
            "authorities": list(AUTHORITIES),
            "admits": t["admits"],
            "attempted": t["attempted"],
            "rejected": t["rejected"],
            "minted_budget": t["minted_budget"],
            "windows_rolled": t["windows_rolled"],
            "violations": t["violations"],
            "overshoot": over,
            "keys_tracked": t["keys_tracked"],
            "pending_windows": t["pending_windows"],
            "audits": t["audits"],
        }

    def endpoint_body(self) -> dict:
        """The /v1/debug/ledger body (schema pinned by
        tests/test_debug_schema.py)."""
        t = self.totals()
        with self._lock:
            over = self._overshoot_locked()
            recent = list(self._recent)
            ground = dict(self._ground_truth)
        return {
            "schema_version": LEDGER_SCHEMA_VERSION,
            "enabled": self.enabled,
            "authorities": list(AUTHORITIES),
            "totals": t,
            "overshoot": over,
            "recent_violations": recent,
            "ground_truth": ground,
        }

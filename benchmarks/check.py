"""The comparison that decides `correct`: the answers the daemon gave for
the audited keys, held to the plain oracle started from the same seeded
snapshot.

A key whose calls never overlapped in time is replayed in order, each
request twice — at the instant just before its call was sent and just
after its answers came back — and every field of the daemon's answer must
lie between the two (equal, where the clock does not reach the field).
A LEAKY_BUCKET's stamp snaps to the daemon's clock at every request, so its
two replays are re-anchored after each answer: the fewest tokens it can hold
(last stamp as late, this request as early as the calls allow) and the most
(the reverse), both continued from the `remaining` the daemon reported.
A key that calls raced on has no known order, but its request is the same
every time, so:
  TOKEN_BUCKET  the multiset of (status, remaining) must equal the oracle's
                for that many requests, and reset_time its expiry;
  LEAKY_BUCKET  the admitted count must lie between the oracle's with every
                request at the first instant and the token supply up to the
                last instant.
Raced keys with a behaviour flag are left to the shape check.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Dict, List, Optional

import numpy as np

import oracle
from traffic import Traffic

COLS = ("id", "send_ns", "recv_ns", "status", "limit", "remaining",
        "reset_time", "position", "call")


def _initial_row(resident_row) -> Optional[oracle.Row]:
    if resident_row is None:
        return None
    return oracle.Row(*[int(v) for v in resident_row])


def _replay_same(row0, n, now, **req) -> collections.Counter:
    """(status, remaining) counts of `n` equal requests at `now`, stopping
    at the oracle's fixed point."""
    table = {} if row0 is None else {0: dataclasses.replace(row0)}
    counts = collections.Counter()
    for i in range(n):
        before = dataclasses.astuple(table[0]) if 0 in table else None
        a = oracle.decide(table, 0, now=now, **req)
        after = dataclasses.astuple(table[0]) if 0 in table else None
        if before == after:
            counts[(a.status, a.remaining)] += n - i
            break
        counts[(a.status, a.remaining)] += 1
    return counts


def check_audits(audits: np.ndarray, traffic: Traffic, stamp_ms: int
                 ) -> Dict[str, object]:
    """`audits`: int64[n, 9] rows as COLS, from every load-generator
    process. Returns the counts and the first mismatches as strings."""
    bad: List[str] = []
    out = {"audited_answers": int(len(audits)), "audited_keys": 0,
           "sequential_keys": 0, "raced_keys": 0, "raced_left_to_shape": 0}
    if not len(audits):
        out["mismatches"], out["examples"] = 0, []
        return out
    a = audits[np.lexsort((audits[:, 7], audits[:, 1], audits[:, 0]))]
    ids, starts = np.unique(a[:, 0], return_index=True)
    ends = np.append(starts[1:], len(a))
    f = traffic.model.fields(ids)
    beh = traffic.behavior_of(ids)
    rows0 = traffic.model.resident_rows(ids, stamp_ms)
    dur = traffic.model.duration_ms
    mismatches = 0
    out["audited_keys"] = int(len(ids))
    for k, (kid, s, e) in enumerate(zip(ids.tolist(), starts, ends)):
        r = a[s:e]
        req = dict(hits=int(f["hits"][k]), limit=int(f["limit"][k]),
                   duration=dur, algorithm=int(f["algorithm"][k]),
                   behavior=int(beh[k]))
        row0 = _initial_row(rows0[k] if kid < traffic.residents else None)
        raced = bool(np.any((r[1:, 1] < r[:-1, 2]) & (r[1:, 8] != r[:-1, 8])))
        if not raced:
            out["sequential_keys"] += 1
            wrong = _check_ordered(kid, r, row0, req)
        elif req["behavior"]:
            out["raced_left_to_shape"] += 1
            wrong = []
        else:
            out["raced_keys"] += 1
            wrong = _check_raced(kid, r, row0, req)
        mismatches += len(wrong)
        if len(bad) < 10:
            bad.extend(wrong[:10 - len(bad)])
    out["mismatches"], out["examples"] = mismatches, bad
    return out


def _check_ordered(kid, r, row0, req) -> List[str]:
    early = {} if row0 is None else {0: dataclasses.replace(row0)}
    late = {} if row0 is None else {0: dataclasses.replace(row0)}
    leaky = req["algorithm"] == oracle.LEAKY_BUCKET
    bad = []
    for rec in r.tolist():
        t0, t1 = rec[1] // 1_000_000, -(-rec[2] // 1_000_000)
        x = oracle.decide(early, 0, now=t0, **req)
        y = oracle.decide(late, 0, now=t1, **req)
        if leaky:
            # continue from what the daemon said it holds; `early` keeps
            # the latest stamp the calls allow (least leak to come), `late`
            # the earliest
            had = rec[5] + (req["hits"] if rec[3] == oracle.UNDER_LIMIT else 0)
            for table, stamp in ((early, t1), (late, t0)):
                table[0].remaining = rec[5]
                if req["hits"] and had:
                    table[0].stamp = stamp
        for name, got in zip(("status", "limit", "remaining", "reset_time"),
                             rec[3:7]):
            lo, hi = sorted((getattr(x, name), getattr(y, name)))
            if not lo <= got <= hi:
                bad.append(f"key {kid:#x}: {name}={got}, oracle [{lo}, {hi}] "
                           f"(clock {t0}..{t1})")
    return bad


def _check_raced(kid, r, row0, req) -> List[str]:
    n = len(r)
    first, last = int(r[:, 1].min()) // 1_000_000, \
        -(-int(r[:, 2].max()) // 1_000_000)
    got = collections.Counter(zip(r[:, 3].tolist(), r[:, 5].tolist()))
    if req["algorithm"] == oracle.TOKEN_BUCKET:
        want = _replay_same(row0, n, first, **req)
        bad = []
        if got != want:
            bad.append(f"key {kid:#x} (raced, token): answers "
                       f"{sorted(got.items())[:4]}, oracle "
                       f"{sorted(want.items())[:4]} for {n} requests")
        lo = row0.expire_at if row0 else first + req["duration"]
        hi = row0.expire_at if row0 else last + req["duration"]
        off = int(((r[:, 6] < lo) | (r[:, 6] > hi)).sum())
        if off:
            bad.append(f"key {kid:#x} (raced, token): {off} reset_time "
                       f"outside [{lo}, {hi}]")
        return bad
    admitted = sum(c for (status, _), c in got.items()
                   if status == oracle.UNDER_LIMIT)
    least = sum(c for (status, _), c in
                _replay_same(row0, n, first, **req).items()
                if status == oracle.UNDER_LIMIT)
    rate = max(req["duration"] // max(req["limit"], 1), 1)
    tokens0, since = (row0.remaining, row0.stamp) if row0 \
        else (req["limit"], first)
    most = min(n, (tokens0 + max(last - since, 0) // rate)
               // max(req["hits"], 1))
    if not least <= admitted <= most:
        return [f"key {kid:#x} (raced, leaky): admitted {admitted} of {n}, "
                f"oracle [{least}, {most}]"]
    return []

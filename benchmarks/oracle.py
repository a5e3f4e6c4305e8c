"""The plain reference: one request at a time, pure Python, unbounded ints.

A copy of the program's own `gubernator_tpu/ops/oracle.py` (itself written
from the reference's algorithms.go:24-336), kept here so that no later PR
can move what the benchmark compares against. It imports nothing of the
program. One departure from the original: DURATION_IS_GREGORIAN is not
replayed (no traffic mix sends it yet; `traffic.py` refuses one that does).

Semantics kept, quirks included: token OVER_LIMIT is sticky on the stored
row once remaining hits zero and is reported on hits=0 peeks; a request for
more than remains is rejected without deducting; RESET_REMAINING deletes a
token bucket and refills a leaky one; the leak is integer arithmetic
(rate = duration // limit ms a token, leak = elapsed // rate) and the
bucket's stamp snaps to `now` on any non-peek request against a non-empty
bucket.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

TOKEN_BUCKET, LEAKY_BUCKET = 0, 1
UNDER_LIMIT, OVER_LIMIT = 0, 1
RESET_REMAINING = 8


@dataclasses.dataclass
class Row:
    algo: int = -1
    limit: int = 0
    remaining: int = 0
    duration: int = 0
    stamp: int = 0  # token CreatedAt / leaky UpdatedAt
    expire_at: int = 0
    status: int = 0


@dataclasses.dataclass
class Answer:
    status: int
    limit: int
    remaining: int
    reset_time: int


def decide(table: Dict[int, Row], key, *, hits: int, limit: int,
           duration: int, algorithm: int, behavior: int, now: int) -> Answer:
    """Apply one request to `table`, mutating it; returns the answer."""
    reset_rem = bool(behavior & RESET_REMAINING)
    row = table.get(key)
    alive = row is not None and row.algo == algorithm and now <= row.expire_at

    if algorithm == TOKEN_BUCKET:
        if alive:
            if reset_rem:
                del table[key]
                return Answer(UNDER_LIMIT, limit, limit, 0)
            rem = min(row.remaining, limit) if row.limit != limit \
                else row.remaining
            new_exp = row.stamp + duration
            dur_changed = row.duration != duration
            if dur_changed and new_exp < now:
                del table[key]
            else:
                exp = new_exp if dur_changed else row.expire_at
                status_resp = status_store = row.status
                if hits != 0:
                    if rem == 0:
                        status_resp = status_store = OVER_LIMIT
                    elif hits > rem:
                        status_resp = OVER_LIMIT
                    else:
                        rem -= hits
                row.limit, row.remaining, row.duration = limit, rem, duration
                row.expire_at, row.status = exp, status_store
                return Answer(status_resp, limit, rem, exp)
        exp = now + duration
        over = hits > limit
        rem = limit if over else limit - hits
        table[key] = Row(TOKEN_BUCKET, limit, rem, duration, now, exp,
                         UNDER_LIMIT)
        return Answer(OVER_LIMIT if over else UNDER_LIMIT, limit, rem, exp)

    if alive:
        rem = limit if reset_rem else row.remaining
        rate = max(duration // max(limit, 1), 1)
        elapsed = max(now - row.stamp, 0)
        rem = min(limit, rem + elapsed // rate)
        rem_zero = rem == 0
        over = hits > rem
        deduct = hits != 0 and not rem_zero and not over
        if not rem_zero and hits != 0:
            row.stamp = now
        if deduct:
            row.expire_at = now + duration
        new_rem = rem - hits if deduct else rem
        row.limit, row.duration, row.remaining = limit, duration, new_rem
        status = OVER_LIMIT if (rem_zero or (hits != 0 and over)) \
            else UNDER_LIMIT
        return Answer(status, limit, new_rem, now + rate)

    rate = max(duration // max(limit, 1), 1)
    over = hits > limit
    rem = 0 if over else limit - hits
    table[key] = Row(LEAKY_BUCKET, limit, rem, duration, now, now + duration,
                     UNDER_LIMIT)
    return Answer(OVER_LIMIT if over else UNDER_LIMIT, limit, rem, now + rate)

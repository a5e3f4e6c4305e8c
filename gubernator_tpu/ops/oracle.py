"""Sequential pure-Python oracle for the decision kernel.

This is the executable specification of the rate-limit semantics: a direct,
readable, one-request-at-a-time implementation of the behavior the batched
kernel (ops/decide.py) must reproduce. Tests drive random request streams
through both and require bit-identical responses and state.

The semantics follow the reference algorithms (reference: algorithms.go:24-336)
including its quirks:

- token OVER_LIMIT is sticky on the stored row once remaining hits zero,
  and is reported even on hits=0 peeks (algorithms.go:112-115);
- a request for more than remains is rejected WITHOUT deducting
  (algorithms.go:125-129, :273-278);
- a first-ever request with hits > limit stores an undrained token bucket
  (remaining = limit) but an empty leaky bucket (algorithms.go:160-165,:319-323);
- RESET_REMAINING deletes a token bucket but refills a leaky bucket
  (algorithms.go:36-47, :205-207);
- leaky leak math is integer: rate = duration // limit ms/token,
  leak = elapsed // rate (algorithms.go:214,:233-240), and UpdatedAt snaps
  to `now` on any non-peek request against a non-empty bucket — the
  sub-rate elapsed residue is consumed (algorithms.go:261-264).

Documented deviations from the reference (see PARITY.md): leaky expiry is
refreshed as now+duration (the reference's `now*duration` at algorithms.go:287
is an evident typo), leaky reset_time is now+rate on creation too (the
reference returns a bare duration at algorithms.go:315), and rates are
clamped to >= 1ms/token to avoid the reference's division-by-zero panic when
limit > duration.

Validity domain: the oracle computes with python's unbounded ints, while
the kernel (and the reference's Go int64 arithmetic) wraps at 2^63. The
two agree for any inputs whose intermediate sums stay within int64 —
e.g. now + duration, remaining + leak — which is every realistic request
and everything the differential fuzz generates; feed durations near 2^63
and the oracle diverges from BOTH wrap-identical implementations.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import datetime as _dt

from gubernator_tpu.types import (
    ERR_EMPTY_NAME,
    ERR_EMPTY_UNIQUE_KEY,
    Algorithm,
    Behavior,
    RateLimitResp,
    Status,
)
from gubernator_tpu.utils.gregorian import (
    GregorianError,
    gregorian_duration,
    gregorian_expiration,
)

VACANT = -1


@dataclasses.dataclass
class Row:
    """One bucket row — mirrors TableState columns."""

    algo: int = VACANT
    limit: int = 0
    remaining: int = 0
    duration: int = 0
    stamp: int = 0  # token CreatedAt / leaky UpdatedAt
    expire_at: int = 0
    status: int = 0


@dataclasses.dataclass
class OracleResp:
    status: int
    limit: int
    remaining: int
    reset_time: int


def oracle_decide(
    table: Dict[str, Row],
    key: str,
    *,
    hits: int,
    limit: int,
    duration: int,
    algorithm: int,
    behavior: int,
    now: int,
    greg_expire: int = 0,
    greg_interval: int = 0,
) -> OracleResp:
    """Apply one request to `table`, mutating it; returns the response."""
    greg = bool(behavior & Behavior.DURATION_IS_GREGORIAN)
    reset_rem = bool(behavior & Behavior.RESET_REMAINING)

    row = table.get(key)
    # expiry-on-read + algorithm switch both mean "no usable row"
    alive = row is not None and row.algo == algorithm and now <= row.expire_at

    if algorithm == Algorithm.TOKEN_BUCKET:
        if alive:
            assert row is not None
            if reset_rem:
                del table[key]
                return OracleResp(Status.UNDER_LIMIT, limit, limit, 0)
            rem = min(row.remaining, limit) if row.limit != limit else row.remaining
            new_exp = greg_expire if greg else row.stamp + duration
            dur_changed = row.duration != duration
            if dur_changed and new_exp < now:
                del table[key]
                alive = False  # fall through to create
            else:
                exp = new_exp if dur_changed else row.expire_at
                status_resp = row.status
                status_store = row.status
                if hits != 0:
                    if rem == 0:
                        status_resp = status_store = Status.OVER_LIMIT
                    elif hits > rem:
                        status_resp = Status.OVER_LIMIT
                    else:
                        rem -= hits
                row.limit = limit
                row.remaining = rem
                row.duration = duration
                row.expire_at = exp
                row.status = status_store
                return OracleResp(status_resp, limit, rem, exp)
        # vacant / expired / switched / recreated
        exp = greg_expire if greg else now + duration
        over = hits > limit
        rem = limit if over else limit - hits
        table[key] = Row(
            algo=Algorithm.TOKEN_BUCKET,
            limit=limit,
            remaining=rem,
            duration=duration,
            stamp=now,
            expire_at=exp,
            status=Status.UNDER_LIMIT,
        )
        return OracleResp(
            Status.OVER_LIMIT if over else Status.UNDER_LIMIT, limit, rem, exp
        )

    # ---- leaky bucket ----
    if alive:
        assert row is not None
        rem = limit if reset_rem else row.remaining
        dur = greg_expire - now if greg else duration
        rate = max((greg_interval if greg else duration) // max(limit, 1), 1)
        elapsed = max(now - row.stamp, 0)
        rem = min(limit, rem + elapsed // rate)
        rem_zero = rem == 0
        over = hits > rem
        deduct = hits != 0 and not rem_zero and not over
        if not rem_zero and hits != 0:
            row.stamp = now
        if deduct:
            row.expire_at = now + dur
        new_rem = rem - hits if deduct else rem
        row.limit = limit
        row.duration = dur
        row.remaining = new_rem
        status = (
            Status.OVER_LIMIT
            if (rem_zero or (hits != 0 and over))
            else Status.UNDER_LIMIT
        )
        return OracleResp(status, limit, new_rem, now + rate)

    dur = greg_expire - now if greg else duration
    rate = max(dur // max(limit, 1), 1)
    over = hits > limit
    rem = 0 if over else limit - hits
    table[key] = Row(
        algo=Algorithm.LEAKY_BUCKET,
        limit=limit,
        remaining=rem,
        duration=dur,
        stamp=now,
        expire_at=now + dur,
        status=Status.UNDER_LIMIT,
    )
    return OracleResp(
        Status.OVER_LIMIT if over else Status.UNDER_LIMIT, limit, rem, now + rate
    )


# --------------------------------------------------------------- service
# The oracle at the request surface, shared by chip_smoke.py (a daemon
# that reads its own wall clock) and __graft_entry__.dryrun_multichip
# (explicit clock). Pure Python: it must stay importable by a process
# that never initialises a JAX backend.


def oracle_answer(table: Dict[str, Row], req, now: int):
    """One RateLimitReq against `table` at `now`, as the service answers
    it: validation errors first, calendar fields for
    DURATION_IS_GREGORIAN, then the bucket math. GLOBAL is an instruction
    to the cluster tier, not to the bucket: the owner applies the request
    as a plain one."""
    if not req.unique_key:
        return RateLimitResp(error=ERR_EMPTY_UNIQUE_KEY)
    if not req.name:
        return RateLimitResp(error=ERR_EMPTY_NAME)
    ge = gi = 0
    if int(req.behavior) & Behavior.DURATION_IS_GREGORIAN:
        local_now = _dt.datetime.fromtimestamp(now / 1000.0)
        try:
            ge = gregorian_expiration(local_now, req.duration)
            gi = gregorian_duration(local_now, req.duration)
        except GregorianError as e:
            return RateLimitResp(error=str(e))
    r = oracle_decide(
        table, req.hash_key(), hits=req.hits, limit=req.limit,
        duration=req.duration, algorithm=int(req.algorithm),
        behavior=int(req.behavior), now=now, greg_expire=ge,
        greg_interval=gi)
    return RateLimitResp(status=int(r.status), limit=r.limit,
                         remaining=r.remaining, reset_time=r.reset_time)


class BracketOracle:
    """Expected answers of a server whose clock cannot be pinned.

    The caller notes the time just before it sends a batch (`t0`) and
    just after it receives the answers (`t1`); the server decided at some
    instant in between. Two tables replay every batch, one at `t0` and one
    at `t1`. A field the clock does not reach (status, limit, remaining
    of a token bucket inside its duration) comes out the same from both
    and must be matched exactly; a field it does reach (`reset_time`,
    leaky `remaining` around a leak tick) must lie between the two. With
    `t0 == t1` (an engine driven with an explicit clock) every field is
    exact."""

    FIELDS = ("status", "limit", "remaining", "reset_time")

    def __init__(self):
        self.early: Dict[str, Row] = {}
        self.late: Dict[str, Row] = {}

    def replay(self, req, t0: int, t1: int):
        """Apply one request to both tables; returns (early, late)."""
        return (oracle_answer(self.early, req, t0),
                oracle_answer(self.late, req, t1))

    def check(self, reqs, resps, t0: int, t1: int) -> list:
        """Replay `reqs` in order and compare; returns the mismatches as
        strings (empty = every response is one the oracle allows)."""
        bad = []
        if len(reqs) != len(resps):
            return [f"{len(reqs)} requests, {len(resps)} responses"]
        for i, (req, got) in enumerate(zip(reqs, resps)):
            a, b = self.replay(req, t0, t1)
            if got.error != a.error or a.error != b.error:
                bad.append(f"#{i} {req.hash_key()!r}: error {got.error!r}, "
                           f"oracle {a.error!r}")
                continue
            for f in self.FIELDS:
                lo, hi = sorted((int(getattr(a, f)), int(getattr(b, f))))
                if not lo <= int(getattr(got, f)) <= hi:
                    bad.append(
                        f"#{i} {req.hash_key()!r}: {f}={getattr(got, f)}, "
                        f"oracle [{lo}, {hi}] (clock {t0}..{t1})")
        return bad

"""Device-resident key directory: open-addressing probe on the chip.

GRADUATED (round-3; prototype was round-1 review item 6, hardened per the
round-2 verdict item 2). The production engines map key strings to table
slots in the host key directory (native/keydir.cpp) — the admitted
host-side cost at multi-M decisions/s (keydir.cpp:5-8, SURVEY §7 hard
part #1: "without host round-trips per key"). This module moves the probe
on-device: the host ships only an 8-byte hash fingerprint per request,
and the chip resolves (or claims) the slot with a vectorized
open-addressing probe — the slot never returns to the host, feeding
decide() directly in the same compiled program (models/devdir_engine.py).

Design:
- the directory is one i64[C] fingerprint column plus an i64[C] last-use
  stamp column; slot IS the probe position, so directory and bucket table
  share indexing (the bucket row's algo=-1 vacancy remains the state
  authority).
- probe: D candidate positions (h + d) % C gathered in ONE [B, D] gather
  (the row-major lesson: batched gathers beat per-element probes), then a
  branchless first-match / first-empty select.
- fingerprints are fnv1a64 masked to 63 bits, +1 to keep 0 = empty.
- IN-BATCH PRIORITY PASS: two DISTINCT keys claiming one position in the
  same batch are resolved by an argsort pass (duplicate claim positions
  sort adjacent; the highest lane wins, losers demote to the retry lane)
  — no last-scatter-wins races, and no O(C) scratch per window.
- AGED EVICTION: a probe whose candidate window has no match and no
  vacancy claims the LEAST-RECENTLY-USED candidate instead (touch stamps
  maintained on every match/claim), after protecting positions matched or
  claimed this batch. The evicted tenant's bucket simply ends (the host
  directory's LRU semantics); un-evictable probes (every candidate
  touched this very batch) return the retry lane.

Retry lanes (slot == -1) are re-dispatched by the engine in a follow-up
window — by then the contested claims have settled. 63-bit fingerprint
equality of two DISTINCT keys (~2^-63 per pair) aliases them to one
bucket; documented, not defended.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from gubernator_tpu.ops.decide import (
    I32,
    I64,
    ROW_ALGO,
    ROW_EXPIRE,
    load_column,
    pad_to_drop,
)
from gubernator_tpu.utils.fnv import fnv1a_64_str

PROBE_DEPTH = 16  # candidate positions per key; full = retry lane


def key_fingerprint(key: str) -> int:
    """63-bit nonzero fingerprint of a key (0 is the empty sentinel)."""
    return (fnv1a_64_str(key) & ((1 << 63) - 1)) | 1


def make_fingerprints(capacity: int) -> jax.Array:
    return jnp.zeros((capacity,), I64)


def make_touch(capacity: int) -> jax.Array:
    return jnp.zeros((capacity,), I64)


def _claim_winners(claim_ok: jax.Array, cslot: jax.Array) -> jax.Array:
    """In-batch priority pass: among lanes claiming the same position,
    exactly one (the highest lane id) wins. Argsort groups duplicate
    positions adjacently; a lane wins iff its (position, lane) key is the
    last of its position group. O(B log B), no O(C) scratch."""
    B = cslot.shape[0]
    lane = jnp.arange(B, dtype=I64)
    sent = jnp.asarray(jnp.iinfo(jnp.int64).max // 2, I64)
    key = jnp.where(claim_ok, cslot.astype(I64) * B + lane, sent + lane)
    order = jnp.argsort(key)
    sorted_pos = key[order] // B
    is_last = jnp.concatenate(
        [sorted_pos[1:] != sorted_pos[:-1],
         jnp.ones((1,), dtype=bool)])
    won = jnp.zeros((B,), dtype=bool).at[order].set(is_last)
    return won & claim_ok


def probe_assign(
    fps: jax.Array, hashes: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Resolve-or-claim a slot for every key hash, on device (no eviction
    — the standalone building block; engines use probe_assign_evict).

    fps: i64[C] fingerprint column; hashes: i64[B] (0 for padding lanes).
    Returns (new_fps, slot i32[B], fresh bool[B]); slot is -1 for padding
    lanes, probes that exhausted PROBE_DEPTH, and in-batch claim LOSERS
    (distinct keys contesting one empty position — retry next window).
    """
    C = fps.shape[0]
    B = hashes.shape[0]
    active = hashes != 0
    base = jnp.abs(hashes) % C
    # ONE [B, D] gather instead of D sequential probes
    pos = (base[:, None] + jnp.arange(PROBE_DEPTH, dtype=I64)[None, :]) % C
    cand = fps[pos]  # i64[B, D]

    is_match = cand == hashes[:, None]
    is_empty = cand == 0
    big = jnp.asarray(PROBE_DEPTH + 1, I32)
    d_idx = jnp.arange(PROBE_DEPTH, dtype=I32)[None, :]
    first_match = jnp.min(jnp.where(is_match, d_idx, big), axis=1)
    first_empty = jnp.min(jnp.where(is_empty, d_idx, big), axis=1)

    matched = first_match <= PROBE_DEPTH
    claimable = (~matched) & (first_empty <= PROBE_DEPTH)
    depth = jnp.where(matched, first_match, first_empty)
    slot64 = jnp.take_along_axis(
        pos, jnp.minimum(depth, PROBE_DEPTH - 1)[:, None].astype(I64), axis=1
    )[:, 0]

    # in-batch priority pass: distinct keys contesting one empty position
    # (duplicate hashes of the SAME key converge benignly, but the engine
    # never sends same-key duplicates in one window anyway)
    want = active & claimable
    won = _claim_winners(want, slot64)
    ok = active & (matched | won)
    slot = jnp.where(ok, slot64, -1).astype(I32)
    fresh = won

    claim_slot = pad_to_drop(jnp.where(fresh, slot, -1), C)
    new_fps = fps.at[claim_slot].set(
        jnp.where(fresh, hashes, 0), mode="drop")
    return new_fps, slot, fresh


def probe_assign_evict(
    fps: jax.Array, touch: jax.Array, hashes: jax.Array, seq
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """probe_assign + aged (LRU-approximate) eviction: a full candidate
    window claims its least-recently-used position instead of failing.

    `seq` is a per-DISPATCH monotone epoch (NOT wall time: many windows
    run per millisecond, and eviction protection must cover exactly the
    positions matched/claimed THIS batch — a wall-clock stamp would also
    freeze out retries issued in the same millisecond).

    Returns (fps, touch, slot i32[B], fresh bool[B], retry bool[B]);
    retry lanes (in-batch claim losers, un-evictable windows) re-dispatch
    in a follow-up window with a fresh epoch.
    """
    C = fps.shape[0]
    B = hashes.shape[0]
    now = jnp.asarray(seq, I64)
    active = hashes != 0
    base = jnp.abs(hashes) % C
    pos = (base[:, None] + jnp.arange(PROBE_DEPTH, dtype=I64)[None, :]) % C
    cand = fps[pos]

    is_match = (cand == hashes[:, None]) & active[:, None]
    is_empty = cand == 0
    big = jnp.asarray(PROBE_DEPTH + 1, I32)
    d_idx = jnp.arange(PROBE_DEPTH, dtype=I32)[None, :]
    first_match = jnp.min(jnp.where(is_match, d_idx, big), axis=1)
    first_empty = jnp.min(jnp.where(is_empty, d_idx, big), axis=1)
    matched = active & (first_match <= PROBE_DEPTH)
    mslot = jnp.take_along_axis(
        pos, jnp.minimum(first_match, PROBE_DEPTH - 1)[:, None].astype(I64),
        axis=1)[:, 0]

    # protect matched positions from eviction BEFORE victims are chosen:
    # their touch moves to `now`, so no victim this batch can be younger
    mpos = pad_to_drop(jnp.where(matched, mslot, -1), C)
    touch = touch.at[mpos].set(now, mode="drop")

    has_empty = first_empty <= PROBE_DEPTH
    eslot = jnp.take_along_axis(
        pos, jnp.minimum(first_empty, PROBE_DEPTH - 1)[:, None].astype(I64),
        axis=1)[:, 0]
    ctouch = touch[pos]  # AFTER the match-touch scatter
    oldest_d = jnp.argmin(ctouch, axis=1)
    vslot = jnp.take_along_axis(pos, oldest_d[:, None], axis=1)[:, 0]
    vtouch = jnp.take_along_axis(ctouch, oldest_d[:, None], axis=1)[:, 0]
    can_evict = vtouch < now  # strictly older than this batch

    want_claim = active & ~matched
    cslot = jnp.where(has_empty, eslot, vslot)
    claim_ok = want_claim & (has_empty | can_evict)
    won = _claim_winners(claim_ok, cslot)

    slot = jnp.where(matched, mslot,
                     jnp.where(won, cslot, -1)).astype(I32)
    fresh = won
    retry = active & (slot < 0)

    wpos = pad_to_drop(jnp.where(won, cslot, -1), C)
    fps = fps.at[wpos].set(jnp.where(won, hashes, 0), mode="drop")
    touch = touch.at[wpos].set(now, mode="drop")
    return fps, touch, slot, fresh, retry


def refresh_vacancies(fps: jax.Array, table: jax.Array,
                      now_ms) -> jax.Array:
    """Clear fingerprints whose bucket row is vacant or expired — the lazy
    recycling pass (host directory handles this with its LRU; here one
    full-column sweep, amortized across many windows)."""
    dead = (load_column(table, ROW_ALGO) < 0) | (
        jnp.asarray(now_ms, I64) > load_column(table, ROW_EXPIRE))
    return jnp.where(dead, jnp.zeros_like(fps), fps)

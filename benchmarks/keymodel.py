"""The key population of a run, as pure functions of (seed, key id).

A key id is a whole number. Ids below `residents` are the keys restored
into the daemon's table at boot; ids from `NEW_BASE` up are keys the daemon
has never seen. Everything about a key — its strings on the wire, its
limit, algorithm, `hits`, behaviour, and the row the snapshot holds for it —
is computed from the seed and the id, so the snapshot writer, every
load-generator process and the checker agree without passing tables around.

Nothing here imports the program under test.
"""

from __future__ import annotations

import os
import struct

import numpy as np

NAME = "rl"  # RateLimitReq.name of every request
KEY_PREFIX = b"acct:"  # RateLimitReq.unique_key = acct:<8 hex digits>
HASH_PREFIX = NAME.encode() + b"_" + KEY_PREFIX  # the daemon's table key
NEW_BASE = 1 << 24  # worker w's never-seen keys start at NEW_BASE * (w + 1)
_HEX = np.frombuffer(b"0123456789abcdef", np.uint8)
_SHIFTS = np.arange(28, -4, -4, dtype=np.uint64)

# GTSLAB1 framing (one chunk: [u32 n][u64 blob_len][u32 len * n][blob]
# [i64 rows * n * 7], closed by [0][0]); rows are algo, limit, remaining,
# duration, stamp, expire_at, status
_MAGIC = b"GTSLAB1\n"
_VERSION = 1
_CHUNK_ROWS = 1 << 20


def mix(ids, seed: int, salt: int) -> np.ndarray:
    """splitmix64 of (id, seed, salt): the one source of per-key draws."""
    z = np.asarray(ids, np.uint64) + np.uint64(
        (seed * 0x9E3779B97F4A7C15 + salt * 0xD1B54A32D192ED03)
        & 0xFFFFFFFFFFFFFFFF)
    with np.errstate(over="ignore"):
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def key_bytes(prefix: bytes, ids) -> np.ndarray:
    """uint8[n, len(prefix) + 8]: the prefix and 8 hex digits of each id."""
    ids = np.asarray(ids, np.uint64)
    out = np.empty((len(ids), len(prefix) + 8), np.uint8)
    out[:, :len(prefix)] = np.frombuffer(prefix, np.uint8)
    out[:, len(prefix):] = _HEX[
        ((ids[:, None] >> _SHIFTS) & np.uint64(15)).astype(np.int64)]
    return out


class KeyModel:
    """Per-key request fields and snapshot rows for one configuration's
    `key_model` and one seed."""

    def __init__(self, params: dict, seed: int):
        self.seed = int(seed)
        self.limits = np.asarray(params["limits"], np.int64)
        self.algorithms = np.asarray(params["algorithms"], np.int64)
        self.hits = np.asarray(params["hits"], np.int64)
        self.duration_ms = int(params["duration_ms"])
        self.used_share_max = float(params["resident_used_share_max"])

    # ---- what a request for a key carries

    def fields(self, ids) -> dict:
        h = mix(ids, self.seed, 1)
        limit = self.limits[(h % np.uint64(len(self.limits))).astype(np.int64)]
        algo = self.algorithms[
            ((h >> np.uint64(8)) % np.uint64(len(self.algorithms))
             ).astype(np.int64)]
        hits = self.hits[
            ((h >> np.uint64(16)) % np.uint64(len(self.hits))
             ).astype(np.int64)]
        return {"limit": limit, "algorithm": algo, "hits": hits}

    def unique_keys(self, ids) -> np.ndarray:
        """uint8[n, 13]: b'acct:' + 8 hex digits of the id."""
        return key_bytes(KEY_PREFIX, ids)

    # ---- what the snapshot holds for a resident key

    def resident_rows(self, ids, stamp_ms: int, extra_tokens: int = 0
                      ) -> np.ndarray:
        """int64[n, 7] snapshot rows: a bucket created at `stamp_ms` that
        has already spent a seeded part of its limit. `extra_tokens` is the
        control's fault: every bucket keeps that many tokens it should not
        have (a table that lost hits)."""
        f = self.fields(ids)
        u = (mix(ids, self.seed, 2) >> np.uint64(11)).astype(np.float64) \
            / float(1 << 53)
        used = np.floor(u * self.used_share_max * f["limit"]).astype(np.int64)
        rows = np.zeros((len(f["limit"]), 7), np.int64)
        rows[:, 0] = f["algorithm"]
        rows[:, 1] = f["limit"]
        rows[:, 2] = f["limit"] - used + extra_tokens
        rows[:, 3] = self.duration_ms
        rows[:, 4] = stamp_ms
        rows[:, 5] = stamp_ms + self.duration_ms
        return rows


def write_snapshot(path: str, model: KeyModel, residents: int, stamp_ms: int,
                   extra_tokens: int = 0) -> int:
    """Write the residents' rows as a GTSLAB1 file, a million rows to a
    chunk, and return its size in bytes."""
    key_len = len(HASH_PREFIX) + 8
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(_MAGIC + struct.pack("<I", _VERSION))
        for lo in range(0, residents, _CHUNK_ROWS):
            ids = np.arange(lo, min(lo + _CHUNK_ROWS, residents),
                            dtype=np.uint64)
            n = len(ids)
            f.write(struct.pack("<IQ", n, n * key_len))
            f.write(np.full(n, key_len, np.uint32).tobytes())
            f.write(key_bytes(HASH_PREFIX, ids).tobytes())
            f.write(model.resident_rows(ids, stamp_ms, extra_tokens).tobytes())
        f.write(struct.pack("<IQ", 0, 0))
    os.replace(tmp, path)
    return os.path.getsize(path)

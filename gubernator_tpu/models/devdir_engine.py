"""Engine variant with the DEVICE-resident key directory (GUBER_DEVICE_DIRECTORY).

The standard Engine resolves key strings to table slots in the host C++
directory before every window — the host-side cost at multi-M
decisions/s. This engine ships only an 8-byte fingerprint per request and
lets the chip resolve (or claim, or LRU-evict) the slot inside the SAME
compiled program that decides the window (ops/devdir.py
probe_assign_evict -> ops/decide.py decide_packed): zero host round trips
per key, which matters when host CPU — not the device — is the serving
bottleneck (DESIGN.md "Device-resident key lookup").

Semantics: responses are bit-identical to the host-directory Engine
(differential-fuzzed, tests/test_devdir_engine.py) with two documented
deviations: eviction is aged (least-recently-used among a key's
PROBE_DEPTH candidates) rather than a global LRU, and two distinct keys
with equal 63-bit fingerprints (~2^-63/pair) alias to one bucket.
In-batch claim conflicts between distinct keys retry in a follow-up
window (bounded; then an error response, never a wrong slot).

Not supported (the device keeps no key strings): Store/Loader hooks and
snapshots — a daemon configured with both fails at boot, honestly.
"""

from __future__ import annotations

import functools as _functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.models.engine import Engine, _bucket_width
from gubernator_tpu.ops.decide import I64, decide_packed
from gubernator_tpu.ops.devdir import (
    key_fingerprint,
    make_fingerprints,
    make_touch,
    probe_assign_evict,
    refresh_vacancies,
)
from gubernator_tpu.types import RateLimitResp

_SWEEP_EVERY = 256  # rounds between fingerprint vacancy sweeps (hygiene)


def _devdir_decide(fps, touch, state, packed, hashes, now_ms, seq):
    """Fused probe + decide: one dispatch, the slot never leaves HBM.
    `seq` is the per-dispatch eviction epoch (ops/devdir.py)."""
    fps, touch, slot, fresh, retry = probe_assign_evict(
        fps, touch, hashes, seq)
    packed = packed.at[0, :].set(slot.astype(I64))
    packed = packed.at[8, :].set(fresh.astype(I64))
    state, out = decide_packed(state, packed, now_ms)
    return fps, touch, state, out, retry


@_functools.lru_cache(maxsize=None)
def _jit_devdir_decide(donate: bool):
    return jax.jit(
        _devdir_decide, donate_argnums=(0, 1, 2) if donate else ())


@_functools.lru_cache(maxsize=None)
def _jit_refresh(donate: bool):
    return jax.jit(
        refresh_vacancies, donate_argnums=(0,) if donate else ())


class DevDirEngine(Engine):
    """Engine with the on-device key directory (see module docstring)."""

    PROBE_RETRIES = 3

    def __init__(self, capacity: int = 1 << 20, min_width: int = 64,
                 max_width: int = 8192, donate: Optional[bool] = None,
                 **kw):
        if kw.get("store") is not None or kw.get("loader") is not None:
            raise ValueError(
                "GUBER_DEVICE_DIRECTORY keeps no key strings on the host: "
                "Store/Loader persistence needs the host directory")
        kw.pop("store", None)
        kw.pop("loader", None)
        super().__init__(capacity=capacity, min_width=min_width,
                         max_width=max_width, donate=donate, **kw)
        # the host directory is unused; the python pipeline feeds windows
        self._prep_fast = None
        self.fps = make_fingerprints(capacity)
        self.touch = make_touch(capacity)
        self.device["key_directory"] = "device"
        self._devdir_step = _jit_devdir_decide(self.donate)
        self._refresh = _jit_refresh(self.donate)
        self._rounds_since_sweep = 0
        self._probe_seq = 0  # per-dispatch eviction epoch (starts > 0)
        from gubernator_tpu import native

        if native.available():  # C fingerprint batch; python twin otherwise
            self._fingerprints = native.fingerprint_batch
        else:
            self._fingerprints = lambda keys: np.fromiter(
                (key_fingerprint(k) for k in keys), np.int64,
                count=len(keys))

    def key_count(self) -> int:
        """Occupied device-directory slots (nonzero fingerprints). One
        device reduction — scrape-path only, never the serving path."""
        with self._lock:
            return int(jnp.count_nonzero(self.fps))

    # directory-dependent surfaces are honestly unsupported
    def snapshot(self, include_expired: bool = False):
        raise RuntimeError(
            "DevDirEngine keeps no key strings; snapshots need the host "
            "directory engine")

    def supports_columnar(self) -> bool:
        return False

    def warmup(self) -> None:
        """Compile the fused probe+decide program per width bucket."""
        widths = []
        w = self.min_width
        while w < self.max_width:
            widths.append(w)
            w *= 2
        widths.append(self.max_width)
        resp = None
        with self._lock:
            for width in widths:
                packed = np.zeros((9, width), np.int64)
                hashes = np.zeros(width, np.int64)
                self._probe_seq += 1
                self.fps, self.touch, self.state, resp, _ = \
                    self._devdir_step(self.fps, self.touch, self.state,
                                      packed, hashes, 0,
                                      self._probe_seq)
            if resp is not None:
                jax.block_until_ready(resp)

    # ------------------------------------------------------------- internals

    def _split_scannable(self, windows):
        # scan coalescing presumes host-resolved slots; every window rides
        # the fused per-round program here
        return windows, []

    def load_snapshot(self, items) -> int:
        items = list(items)
        if items:
            raise RuntimeError(
                "DevDirEngine cannot seed from snapshots (host directory "
                "unused); start it empty or use the host-directory engine")
        return 0

    def _apply_round(self, round_work, now_ms, responses,
                     skip_store: bool = False, resolved=None) -> None:
        """Probe/retry dispatch of one window. Caller holds the engine
        lock (fps/touch/state are donated and rebound each step)."""
        import time as _time

        stage = self.stats.stage_ns
        if self._rounds_since_sweep >= _SWEEP_EVERY:
            self._rounds_since_sweep = 0
            self.fps = self._refresh(self.fps, self.state, now_ms)
        work = list(round_work)
        for _attempt in range(self.PROBE_RETRIES + 1):
            n = len(work)
            w = _bucket_width(n, self.min_width, self.max_width)
            t0 = _time.perf_counter_ns()
            packed = np.zeros((9, w), np.int64)
            if n:
                packed[1:8, :n] = np.array(
                    [(r.hits, r.limit, r.duration, int(r.algorithm),
                      int(r.behavior), ge, gi)
                     for _i, r, ge, gi in work], np.int64).T
            hashes = np.zeros(w, np.int64)
            if n:
                hashes[:n] = self._fingerprints(
                    [it[1].hash_key() for it in work])
            t1 = _time.perf_counter_ns()
            stage["pack"] += t1 - t0
            self._probe_seq += 1  # fresh epoch per dispatch: a retry can
            # evict what the previous attempt touched, so it terminates
            self.fps, self.touch, self.state, out, retry = \
                self._devdir_step(self.fps, self.touch, self.state,
                                  packed, hashes, now_ms, self._probe_seq)
            out = np.asarray(out)
            retry = np.asarray(retry)
            t2 = _time.perf_counter_ns()
            stage["device"] += t2 - t1
            self.stats.rounds += 1
            self._rounds_since_sweep += 1

            nxt = []
            status, limit, remaining, reset = out[:, :n].tolist()
            rt = retry[:n].tolist()
            for j, item in enumerate(work):
                if rt[j]:
                    nxt.append(item)
                    continue
                st = status[j]
                if st == 1:
                    self.stats.over_limit += 1
                responses[item[0]] = RateLimitResp(
                    status=st, limit=limit[j], remaining=remaining[j],
                    reset_time=reset[j])
            stage["demux"] += _time.perf_counter_ns() - t2
            work = nxt
            if not work:
                return
        for item in work:  # bounded: never a wrong slot, an honest error
            self.stats.errors += 1
            responses[item[0]] = RateLimitResp(
                error="device directory contention: probe window "
                      "exhausted after retries")

    def global_registry_size(self) -> int:  # metrics hook parity
        return 0

"""Single-host rate-limit engine: host batching over the device kernel.

This is the TPU-native analogue of the reference's core request path
(reference: gubernator.go:110-224 fan-out + algorithms.go under one mutex):
instead of 1000 goroutines contending on a lock, a request batch becomes one
device program. The engine owns:

- the device key table (ops/decide.py row-major u32[C, 16] rows in HBM);
- the host key directory (models/keyspace.py);
- duplicate-key *rounds*: the reference's mutex serializes same-key requests
  inside a batch; we split a window so each kernel call touches each slot at
  most once, preserving exact sequential semantics (occurrence k of a key
  goes to round k);
- width bucketing: batches are padded to power-of-two widths so XLA compiles
  a handful of programs, then reuses them;
- the Store/Loader persistence hooks (store.py; reference: store.go).

The engine is synchronous and thread-safe via one lock — the service layer
(service/) puts the async micro-batching window in front of it.
"""

from __future__ import annotations

import functools as _functools
import threading
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from gubernator_tpu.obs import witness
from gubernator_tpu.models.keyspace import (
    KeyDirectory,
    peek_slots,
    resolve_slots,
)
from gubernator_tpu.models.prep import (
    bucket_pow2 as _bucket_pow2,
    bucket_width as _bucket_width,
    preprocess,
)
from gubernator_tpu.ops.decide import (
    I32,
    I64,
    ROW_HITS,
    TableState,
    compact_window,
    decide_packed_lean,
    decide_scan_carried,
    decide_scan_carried_compact,
    decide_scan_carried_lean,
    decide_scan_packed_lean,
    LEAN_REFUSALS,
    lean_capacity_ok,
    lean_stage,
    lean_window,
    staging_policy,
    decide_packed,
    decide_packed_compact,
    decide_scan_packed,
    decide_scan_packed_compact,
    kernel_telemetry,
    fetch_rows,
    host_rows,
    load_rows,
    make_table,
    pack_window,
    pad_window,
    store_rows,
    widen_compact_out,
)
from gubernator_tpu.native import PREP_OVERCOMMIT
from gubernator_tpu.obs.profile import Profiler
from gubernator_tpu.store import BucketSnapshot, Loader, Store
from gubernator_tpu.types import (
    SLOW_PATH_BEHAVIOR_MASK as _NATIVE_SINGLE_SLOW_MASK,
    Behavior,
    RateLimitReq,
    RateLimitResp,
)
from gubernator_tpu.utils.interval import millisecond_now
from gubernator_tpu.utils.platform import release_compile_memory

_GREG_MASK = int(Behavior.DURATION_IS_GREGORIAN)


def _inject_rows(state: TableState, slot, algo, limit, remaining, duration,
                 stamp, expire_at, status) -> TableState:
    """Scatter host-provided rows into the table (store read-through/loader)."""
    rows = jnp.stack(
        [algo.astype(I64), limit, remaining, duration, stamp, expire_at,
         status.astype(I64), jnp.zeros_like(limit)],
        axis=1,
    )
    return store_rows(state, slot, rows)


def _gather_rows(state: TableState, slot):
    """Fetch rows for store write-through / snapshotting (7-column tuple,
    TableState row field order)."""
    rows = load_rows(state, jnp.maximum(slot, 0))
    return tuple(rows[:, i] for i in range(7))


# Jitted callables are shared process-wide (keyed by donate flag) so N
# engines in one process — the in-process cluster harness boots several —
# compile each batch width once, not once per engine.
@_functools.lru_cache(maxsize=None)
def _jit_decide_packed(donate: bool):
    return jax.jit(decide_packed, donate_argnums=(0,) if donate else ())


@_functools.lru_cache(maxsize=None)
def _jit_decide_packed_compact(donate: bool):
    return jax.jit(decide_packed_compact,
                   donate_argnums=(0,) if donate else ())


@_functools.lru_cache(maxsize=None)
def _jit_decide_packed_lean(donate: bool):
    return jax.jit(decide_packed_lean,
                   donate_argnums=(0,) if donate else ())


@_functools.lru_cache(maxsize=None)
def _jit_scans(donate: bool, carried: bool):
    """The scan programs as (wide, compact, lean): with the table as the
    carry (any windows), or the rows (`carried`: a lane-aligned group's;
    ops/decide.py _scan_carried)."""
    fns = ((decide_scan_carried, decide_scan_carried_compact,
            decide_scan_carried_lean) if carried else
           (decide_scan_packed, decide_scan_packed_compact,
            decide_scan_packed_lean))
    return tuple(jax.jit(fn, donate_argnums=(0,) if donate else ())
                 for fn in fns)


@_functools.lru_cache(maxsize=None)
def _jit_inject(donate: bool):
    return jax.jit(_inject_rows, donate_argnums=(0,) if donate else ())


@_functools.lru_cache(maxsize=None)
def _jit_gather():
    return jax.jit(_gather_rows)


@_functools.lru_cache(maxsize=None)
def _jit_slab(rows: int):
    """Fixed-shape row-slab fetch for the streamed snapshot: one compiled
    program regardless of table size or start offset."""
    return jax.jit(lambda st, i: jax.lax.dynamic_slice_in_dim(st, i, rows,
                                                              axis=0))


class EngineStats:
    """Counters plus a cumulative per-stage wall-clock breakdown.

    The stage clocks (nanoseconds) split a window's host path — validate/
    round-split (`prep`), key-directory resolution (`lookup`), Store
    read-through/write-through I/O (`store`), staging-buffer fill (`pack`),
    kernel dispatch + readback (`device`), response demux (`demux`) — so an
    operator can see WHERE a slow window went without a profiler attached.
    Lock-acquisition waits are deliberately excluded (deltas are computed
    before entering the engine lock). Exposed as
    engine_stage_seconds_total{stage=...} in /metrics (the reference has no
    tracing tier at all, SURVEY §5.1).

    The scan counters tell a repeated key's tail from plain traffic:
    `rounds` counts the windows that held a lane, `scan_dispatches` the
    device calls that carried more than one window (decide() under
    lax.scan), `scan_rounds` the rounds those retired (the pads of a
    power-of-two stack not counted), `scan_lanes_live` the lanes of those
    rounds that decided a request and `scan_lanes` what the dispatches put
    on the device (depth as launched x width), `scan_rounds_carried` the
    scan rounds whose rows rode the scan's carry (one row gather and one
    row scatter a dispatch, not one of each a round). rounds - scan_rounds
    rode a launch of their own, scan_rounds - scan_rounds_carried rode the
    table, the rest the carry.

    The link counters are bumped in the funnels every launch and every
    fetch goes through (Engine._launch, _fetch_staged): `staged_bytes` the
    host arrays handed to the program (their nbytes, whichever wire format
    carried the window), `fetched_bytes` what was copied back (the device
    array's nbytes, before widening), `staged_lanes` the lanes the host
    walked to make the staged arrays (depth x the launch's live prefix; x
    the launched width where the wide format ships whole): beside
    `scan_lanes` and the kernel telemetry's widths, what the host touched
    of what it launched.

    The lean counters say why a deployment is off the 4-byte lane
    (ops/decide.py lean_stage): `lean_refused_<reason>`, one of
    LEAN_REFUSALS, counts the launches under GUBER_STAGING=auto that the
    lane refused for that reason (the first that held; a refused launch
    rides compact or wide), and `lean_tuples` is the most config rows a
    lean launch's table has held, of LEAN_MAX_CFG."""

    STAGES = ("prep", "lookup", "store", "pack", "device", "demux")

    def __init__(self):
        self.requests = 0
        self.batches = 0
        self.rounds = 0
        self.over_limit = 0
        self.errors = 0
        self.native_singles = 0  # lone requests decided in C (no dispatch)
        self.scan_dispatches = 0
        self.scan_rounds = 0
        self.scan_rounds_carried = 0
        self.scan_lanes_live = 0
        self.scan_lanes = 0
        self.staged_bytes = 0
        self.staged_lanes = 0
        self.fetched_bytes = 0
        self.lean_refused = {why: 0 for why in LEAN_REFUSALS}
        self.lean_tuples = 0
        self.stage_ns = {s: 0 for s in self.STAGES}

    def note_scan(self, rounds: int, live: int, lanes: int,
                  carried: bool = False) -> None:
        """One scan dispatch that retires `rounds` rounds holding `live`
        lanes, launched `lanes` wide in all, the rows `carried` through it
        or not. Caller holds the engine lock."""
        self.scan_dispatches += 1
        self.scan_rounds += rounds
        self.scan_rounds_carried += rounds if carried else 0
        self.scan_lanes_live += live
        self.scan_lanes += lanes

    def as_dict(self) -> Dict[str, int]:
        d = dict(requests=self.requests, batches=self.batches,
                 rounds=self.rounds, over_limit=self.over_limit,
                 errors=self.errors, native_singles=self.native_singles,
                 scan_dispatches=self.scan_dispatches,
                 scan_rounds=self.scan_rounds,
                 scan_rounds_carried=self.scan_rounds_carried,
                 scan_lanes_live=self.scan_lanes_live,
                 scan_lanes=self.scan_lanes,
                 staged_bytes=self.staged_bytes,
                 staged_lanes=self.staged_lanes,
                 fetched_bytes=self.fetched_bytes,
                 lean_tuples=self.lean_tuples)
        for why, n in self.lean_refused.items():
            d[f"lean_refused_{why}"] = n
        for s, ns in self.stage_ns.items():
            d[f"{s}_ns"] = ns
        return d


class Engine:
    """One device's (or host's) authoritative rate-limit state + kernel."""

    def __init__(
        self,
        capacity: int = 1 << 20,
        store: Optional[Store] = None,
        loader: Optional[Loader] = None,
        min_width: int = 64,
        max_width: int = 8192,
        donate: Optional[bool] = None,
    ):
        self.capacity = capacity
        self.state = make_table(capacity)
        from gubernator_tpu import native
        from gubernator_tpu.native import make_key_directory

        self.directory = make_key_directory(capacity)
        # native one-pass window prep: only over the C++ directory (it calls
        # the KeyDir handle directly); python-directory engines keep the
        # python pipeline
        self._prep_fast = (
            native.prep_pack_fast
            if isinstance(self.directory, native.NativeKeyDirectory)
            else None
        )
        self.store = store
        self.loader = loader
        self.min_width = min_width
        # one kernel round must never need more distinct slots than exist
        self.max_width = min(max_width, capacity)
        self.stats = EngineStats()
        # daemon-registry histograms (service/metrics.py); attached by the
        # daemon/harness after construction, None keeps every observation
        # site a no-op
        self.metrics = None
        # hot-key detector (service/leases.py HotKeyTracker); attached by
        # LeaseManager.arm() when GUBER_HOT_LEASES is set — same None-is-off
        # contract as metrics, so the staging dispatchers stay untouched
        # when the lease tier is disabled
        self.hot_tracker = None
        # continuous cycle profiler (obs/profile.py): lock-wait, prep,
        # dispatch, readback and demux streaming histograms feeding
        # /v1/debug/profile. Always constructed; GUBER_PROFILE=0 turns
        # every observation site into a single attribute test
        self.profiler = Profiler()
        # decision ledger (obs/ledger.py): per-window attribution columns
        # for the conservation auditor; attached by the Instance, None
        # (or a disabled ledger) keeps every window hook a no-op
        self.ledger = None
        self._lock = witness.make_lock("engine")
        if donate is None:
            from gubernator_tpu.utils.platform import donation_supported

            donate = donation_supported()
        self.donate = donate
        self._decide_packed = _jit_decide_packed(donate)
        self._decide_packed_compact = _jit_decide_packed_compact(donate)
        self._decide_packed_lean = _jit_decide_packed_lean(donate)
        self._scans = _jit_scans(donate, False)
        self._scans_carried = _jit_scans(donate, True)
        # lean staging needs every slot to fit the 24-bit lane field
        self._lean_ok = lean_capacity_ok(capacity)
        self._inject = _jit_inject(donate)
        self._gather = _jit_gather()
        # Staging wire-format policy: "auto" (default) ships each window
        # on the leanest eligible wire — lean i32[W] (4 B/lane), compact
        # i32[5, W] (20 B/lane), wide i64[9, W] as the last resort — all
        # held bit-identical by TestLeanStaging/TestCompactStaging.
        self._staging = staging_policy()
        if loader is not None:
            if hasattr(loader, "load_slabs"):
                self.load_snapshot_slabs(loader.load_slabs())
            else:
                self.load_snapshot(loader.load())
        # boot line + /v1/debug/vars (utils/platform.py); placement never
        # changes after construction, so it is read once
        from gubernator_tpu.utils.platform import device_facts

        self.device = device_facts(
            self, "native" if self._prep_fast is not None else "python")

    # ------------------------------------------------------------------ API

    def warmup(self) -> None:
        """Compile the decision kernel for every width bucket up front.

        XLA compiles one program per batch width; without this the first
        request at each width pays seconds of compile latency — fatal inside
        the 500 µs-windowed peer-forwarding path. Daemons call this before
        serving (no reference analogue; compilation is a TPU concern)."""
        # enumerate exactly the widths bucket_width can produce, including
        # the capped terminal width when max_width isn't min_width * 2^k
        widths = []
        w = self.min_width
        while w < self.max_width:
            widths.append(w)
            w *= 2
        widths.append(self.max_width)
        resp = None
        both = self._staging != "wide"
        with self._lock:
            for width in widths:
                packed = np.zeros((9, width), np.int64)
                packed[0, :] = -1  # all padding lanes
                self.state, resp = self._decide_packed(self.state, packed, 0)
                if both:  # auto mode serves from any eligible wire format
                    self.state, resp = self._decide_packed_compact(
                        self.state, compact_window(packed), 0)
                    if self._lean_ok:
                        ln = lean_window(packed, self.capacity)
                        self.state, resp = self._decide_packed_lean(
                            self.state, ln[0], ln[1], 0)
                release_compile_memory()
            # every scan shape _apply_windows_scanned dispatches: depths
            # 2..=_MAX_SCAN, min_width wide; below max_width the row-carried
            # programs (its groups are lane-aligned whenever the rounds
            # come from one preprocess() whose round 0 fits a window: every
            # serving path), at max_width the table-carried ones the group
            # launches share (_carries)
            scans = self._scans_carried if self._carries(self.min_width) \
                else self._scans
            k = 2
            while k <= self._MAX_SCAN:
                resp = self._warm_scan_depth(scans, k, self.min_width)
                k *= 2
            # serving-path auxiliary jits: the lone-miss mirror seed's
            # 1-slot gather and the mirror-flush inject at its common
            # (min-width) bucket. A cold compile of either inside a
            # peerlink/gRPC-front worker stalls a LIVE response for the
            # whole compile (seconds — long enough to be a first-RPC
            # deadline).
            jax.block_until_ready(
                self._gather(self.state, jnp.zeros(1, I32)))
            warm_inject = np.zeros((1, 8), np.int64)
            warm_inject[0, 0] = -1  # dropped lane: compile, mutate nothing
            self._apply_inject_rows(warm_inject)
            if resp is not None:
                jax.block_until_ready(resp)

    def _carries(self, width: int) -> bool:
        """One scan program a shape: scans `max_width` wide belong to the
        group launches (different callers' windows, keys at arbitrary
        lanes: the table as the carry), so a repeated key's rounds ride
        the rows' carry only below it. On a one-width ladder they share
        the group launches' programs, as they always did: a loaded program
        is ~50 MB of the daemon's resident set (9 more read +2.4-3.4% of
        daemon_rss_mb at 8192..8192, PERF.md PR 39)."""
        return width < self.max_width

    def _warm_scan_depth(self, scans, k: int, width: int):
        """Compile the depth-`k`, `width`-lane programs of `scans` (wide,
        compact, lean) by one all-padding launch each: every lane drops,
        the table is untouched. Caller holds the engine lock."""
        wide, compact, lean = scans
        stacked = np.zeros((k, 9, width), np.int64)
        stacked[:, 0, :] = -1
        self.state, resp = wide(self.state, stacked, 0)
        if self._staging != "wide":  # auto serves any eligible wire format
            self.state, resp = compact(
                self.state, compact_window(stacked), 0)
            if self._lean_ok:
                ln = lean_window(stacked, self.capacity)
                self.state, resp = lean(self.state, ln[0], ln[1], 0)
        release_compile_memory()
        return resp

    # -------------------------------------------------- staging dispatch
    # Every window dispatch funnels through these two helpers into
    # _launch, so the wide/compact/lean wire-format switch and the live
    # prefix the host walks live in exactly one place (VERDICT r3 item 1:
    # auto-selected by eligibility).

    def _dispatch_staged(self, packed: np.ndarray, now_ms, live=None):
        """Dispatch one wide-format i64[9, W] window, shipping it lean
        (4 B/lane — the hits==1, few-configs serving shape) when eligible,
        compact (20 B/lane) otherwise, wide as the last resort. `live`:
        lanes [live, W) are padding (slot -1, zeros elsewhere), so the
        host walks the prefix alone; None walks the buffer. Returns an
        opaque handle for _fetch_staged. Caller holds the engine lock
        (self.state is donated and rebound here)."""
        return self._launch(
            "packed", (self._decide_packed, self._decide_packed_compact,
                       self._decide_packed_lean),
            packed, 1, now_ms, live, None)

    def _dispatch_scan_staged(self, stacked: np.ndarray, now_ms,
                              carried: bool = False, live=None, width=None):
        """decide_scan dispatch of a wide i64[K, 9, L] stack, shipped
        lean/compact when eligible. `carried` takes the row-carried
        programs: the caller has aligned the stack's lanes (a lane holds
        one slot through the stack; ops/decide.py _scan_carried). `live`
        as in _dispatch_staged; `width` (default L) is the launched width
        of a stack that holds its live lanes alone. Handle contract
        matches _dispatch_staged. Caller holds the engine lock."""
        return self._launch(
            "carry" if carried else "scan",
            self._scans_carried if carried else self._scans,
            stacked, stacked.shape[0], now_ms, live, width)

    def _launch(self, tag: str, fns, packed: np.ndarray, depth: int, now_ms,
                live, width):
        """The one launch: stage `packed`, a wide window (or a stack `depth`
        windows deep), then call the program of `fns` (wide, compact, lean;
        `tag` names them to the kernel telemetry) that takes what it was
        staged as.

        Every numpy pass the host makes over a launch (the hot tracker's
        feed, the wire-format converters and, through the handle, the
        widening of its answers) runs over its live prefix, whatever width
        it launches at; the shipped array is the one a walk of the whole
        buffer would make, and `staged_lanes` counts what was walked.
        Everything from here to the jitted call is `stage` (`t_in` 0: the
        profiler is off); the call (enqueue and host -> device placement)
        is `launch`, and one pair of clock reads feeds that phase and the
        telemetry's histogram. Caller holds the engine lock."""
        prof = self.profiler
        t_in = time.perf_counter_ns() if prof.enabled else 0
        sub = prof.seams()  # nested in the caller's `dispatch`
        sub("stage")
        src = packed if live is None else packed[..., :live]
        live = src.shape[-1]
        w = width or packed.shape[-1]
        ht = self.hot_tracker
        if ht is not None:
            # the staged rows are already host numpy: two bulk adds per
            # window, no per-key cost (service/leases.py)
            ht.feed_slots(src[..., 0, :], src[..., 1, :])
        # host arrays go to the program as they are: its call path places
        # them, an explicit jnp.asarray first is ~0.3 ms of Python a
        # window under the GIL
        wide, compact, lean = fns
        kernel, fn, staged = tag + "_wide", wide, None
        if self._staging != "wide":
            staged, refused, tuples = lean_stage(src, self.capacity, w)
            if staged is not None:
                kernel, fn = tag + "_lean", lean
                self.stats.lean_tuples = max(self.stats.lean_tuples, tuples)
            else:
                self.stats.lean_refused[refused] += 1
                c = compact_window(src, w)
                if c is not None:
                    kernel, fn, staged = tag + "_compact", compact, (c,)
        if staged is None:
            # the buffer itself where it is the launched width already (the
            # C prep's, pack_window's); a stack of live lanes alone is
            # padded out
            staged = (pad_window(packed, w),)
            lanes, compact_now = depth * w, None
        else:
            lanes, compact_now = depth * live, now_ms
        if kernel_telemetry.needs_probe(kernel, w):
            kernel_telemetry.offer_probe(
                kernel, w, fn, (self.state, *staged, now_ms))
        for a in staged:
            self.stats.staged_bytes += a.nbytes
        self.stats.staged_lanes += lanes
        sub("launch")
        t = time.perf_counter_ns()
        self.state, out = fn(self.state, *staged, now_ms)
        t2 = time.perf_counter_ns()
        sub(None)
        kernel_telemetry.note(kernel, w, depth=depth, dur_ns=t2 - t)
        if t_in:
            prof.observe_sub("stage", t - t_in)
            prof.observe_sub("launch", t2 - t)
        return out, compact_now, live

    def _obs_device(self, ns: int, lanes: int) -> None:
        """Feed one window's device dispatch+readback wall time and live
        lane count into the daemon-registry histograms (no-op until a
        Metrics is attached)."""
        m = self.metrics
        if m is not None:
            m.engine_device_dispatch_ms.observe(ns / 1e6)
            m.engine_window_lanes.observe(lanes)

    def key_count(self) -> int:
        """Live key-table occupancy (the cache_size /
        engine_key_table_size gauge source)."""
        return len(self.directory)

    def kernel_fingerprints(self) -> Dict[str, str]:
        """HLO fingerprints of the canonical decision programs: the wide
        per-window kernel and the two depth-2 scans (the table and the
        rows as the carry) at min_width. Every
        staging variant lowers from the same decide body, so any kernel
        change — a jax/libtpu bump, a decide.py edit, an XLA flag drift
        — shows here. Boot-time introspection only (cmd/daemon.py
        compares across boots and emits profile.recompile on drift);
        lowering traces but never compiles."""
        from gubernator_tpu.obs.profile import hlo_fingerprint

        with self._lock:
            state_aval = jax.ShapeDtypeStruct(self.state.shape,
                                              self.state.dtype)
        w = self.min_width
        out: Dict[str, str] = {}
        try:
            packed = jax.ShapeDtypeStruct((9, w), I64)
            out[f"packed_wide@{w}"] = hlo_fingerprint(
                self._decide_packed.lower(
                    state_aval, packed, 0).as_text())
            stacked = jax.ShapeDtypeStruct((2, 9, w), I64)
            out[f"scan_wide@{w}"] = hlo_fingerprint(
                self._scans[0].lower(
                    state_aval, stacked, 0).as_text())
            out[f"carry_wide@{w}"] = hlo_fingerprint(
                self._scans_carried[0].lower(
                    state_aval, stacked, 0).as_text())
        except Exception:  # noqa: BLE001 — introspection must not break boot
            pass
        return out

    def _fetch_staged(self, handle):
        """Block on a dispatched window and return (the wide i64 response
        rows regardless of which wire format carried it, the bytes copied
        back for them). Needs no lock; the caller adds the bytes to
        `fetched_bytes` where it holds one.

        The copy waits for the device by itself. While a capture runs the
        wait is made apart from it (`device_wait`, then the copy and its
        widening as `fetch`: both inside the caller's `readback`); that is
        a second release of the GIL a window, ~1% of a busy daemon's rate
        (PERF.md section 6, PR 40), so nobody pays it otherwise."""
        out, compact_now, live = handle
        prof = self.profiler
        split = prof.capturing
        if split:
            sub = prof.seams()  # nested in the caller's `readback`
            sub("device_wait")
            t0 = time.perf_counter_ns()
            out.block_until_ready()
            t1 = time.perf_counter_ns()
            sub("fetch")
        rows = (np.asarray(out) if compact_now is None
                else widen_compact_out(out, compact_now, live))
        if split:
            t2 = time.perf_counter_ns()
            sub(None)
            prof.observe_sub("device_wait", t1 - t0)
            prof.observe_sub("fetch", t2 - t1)
        return rows, out.nbytes

    def get_rate_limits(
        self, requests: Sequence[RateLimitReq], now_ms: Optional[int] = None
    ) -> List[RateLimitResp]:
        """Decide a batch. Exact per-key sequential semantics, any batch size."""
        if now_ms is None:
            now_ms = millisecond_now()
        if (self._prep_fast is not None and self.store is None
                and 0 < len(requests) <= self.max_width):
            fast = self._fast_window(requests, now_ms)
            if fast is not None:
                return fast
        return self._slow_window(requests, now_ms)

    def _slow_window(self, requests, now_ms,
                     count_batch: bool = True) -> List[RateLimitResp]:
        """The python pipeline: full validation, gregorian precompute, and
        duplicate-key round splitting (models/prep.py). `count_batch` is
        False when called as a fast window's leftover tail — the client
        batch was already counted there."""
        prof = self.profiler
        seams = prof.seams()  # host spans, while a capture runs
        seams("prep")
        t0 = time.perf_counter_ns()
        responses, rounds, n_errors = preprocess(requests, now_ms)
        prep_ns = time.perf_counter_ns() - t0  # excludes the lock wait below
        prof.observe("prep", prep_ns)
        tq = time.perf_counter_ns() if prof.enabled else 0
        seams("lock_wait")
        with self._lock:
            if tq:
                t0 = time.perf_counter_ns()
                prof.lock_wait("slow_window", t0 - tq)
            seams(None)  # each round writes its own chain from here
            self.stats.stage_ns["prep"] += prep_ns
            self.stats.requests += len(requests)
            self.stats.batches += 1 if count_batch else 0
            self.stats.errors += n_errors
            windows = []
            for round_work in rounds:
                self.stats.rounds += 1
                for start in range(0, len(round_work), self.max_width):
                    windows.append(round_work[start:start + self.max_width])
            head, tail = self._split_scannable(windows)
            for wk in head:
                self._apply_round(wk, now_ms, responses)
            if tail:
                self._apply_windows_scanned(tail, now_ms, responses)
            if tq:
                prof.lock_hold("slow_window", time.perf_counter_ns() - t0)
        return responses  # type: ignore[return-value]

    def _fast_window(self, requests, now_ms) -> Optional[List[RateLimitResp]]:
        """Native one-pass window: validate + first-occurrence round split +
        directory lookup + pack in one C call (native/keydir.cpp
        keydir_prep_pack_fast). Lanes the C pass can't take — invalid,
        gregorian, duplicate occurrences — come back as leftover item
        indices and run through the python pipeline AFTER this round, which
        preserves exact per-key sequential semantics. (The lock is released
        between the round and the tail: another caller's window may
        interleave there, exactly as the reference's per-request mutex
        allows between two same-batch goroutines, gubernator.go:126-213,328
        — the python pipeline's whole-batch lock is stricter than both.)
        Returns None only for windows the native path can't start at all
        (nothing mutated)."""
        w = _bucket_width(len(requests), self.min_width, self.max_width)
        prof = self.profiler
        seams = prof.seams()  # host spans, while a capture runs
        seams("alloc")
        packed = np.zeros((9, w), np.int64)
        tq = time.perf_counter_ns() if prof.enabled else 0
        seams("lock_wait")
        with self._lock:
            t0 = time.perf_counter_ns()  # excludes the lock wait
            if tq:
                prof.lock_wait("fast_window", t0 - tq)
            seams("prep")
            n0, lane_item, leftover, inject = self._prep_fast(
                self.directory, requests, packed, _GREG_MASK)
            if n0 == PREP_OVERCOMMIT:
                # mirror rows collected before the abort must still land
                # (unreachable on this engine — max_width <= capacity —
                # but the invariant is cheap to keep)
                self._apply_inject_rows(inject)
                raise RuntimeError(
                    f"key directory over-committed: >{self.capacity} "
                    "distinct keys in one lookup")
            if n0 < 0:
                seams(None)
                if tq:
                    prof.lock_hold("fast_window",
                                   time.perf_counter_ns() - t0)
                return None
            stage = self.stats.stage_ns
            t1 = time.perf_counter_ns()
            stage["prep"] += t1 - t0
            prof.observe("prep", t1 - t0)
            self.stats.requests += n0
            self.stats.batches += 1
            self._apply_inject_rows(inject)
            responses: List[Optional[RateLimitResp]] = [None] * len(requests)
            if n0:
                self.stats.rounds += 1
                seams("dispatch")
                staged = self._dispatch_staged(packed, now_ms, n0)
                td = time.perf_counter_ns()
                seams("readback")
                out, nbytes = self._fetch_staged(staged)
                t2 = time.perf_counter_ns()
                seams("demux")
                self.stats.fetched_bytes += nbytes
                stage["device"] += t2 - t1
                self._obs_device(t2 - t1, n0)
                prof.observe("dispatch", td - t1)
                prof.observe("readback", t2 - td)
                status, limit, remaining, reset = out[:, :n0].tolist()
                over = 0
                for j, i in enumerate(lane_item.tolist()):
                    st = status[j]
                    if st == 1:
                        over += 1
                    responses[i] = RateLimitResp(
                        status=st, limit=limit[j], remaining=remaining[j],
                        reset_time=reset[j])
                self.stats.over_limit += over
                demux_ns = time.perf_counter_ns() - t2
                stage["demux"] += demux_ns
                prof.observe("demux", demux_ns)
                led = self.ledger
                if led is not None and led.enabled:
                    led.note_slots(packed, out, n0)
            seams(None)
            if tq:
                prof.lock_hold("fast_window", time.perf_counter_ns() - t0)
        if len(leftover):
            idxs = leftover.tolist()
            tail = self._slow_window(
                [requests[i] for i in idxs], now_ms, count_batch=False)
            for i, resp in zip(idxs, tail):
                responses[i] = resp
        return responses  # type: ignore[return-value]

    # ----------------------------------------------------- pipelined serving
    # The launch/collect split of the request-object path: the combiner
    # (service/combiner.py) keeps up to GUBER_PIPELINE_DEPTH window groups
    # in flight — launch N+1 is admitted while window N's readback is still
    # crossing the link. Per-key sequential semantics survive because (a)
    # launches are serialized under the engine lock, so host prep order ==
    # dispatch order, and (b) the device state chain (each launch consumes
    # the previous launch's table) orders the windows' effects on device —
    # the same argument submit_columnar already rides. Leftover lanes
    # (duplicate occurrences, gregorian, invalid) are retired AT LAUNCH,
    # between this group's dispatch and any later launch, so a key's later
    # arrivals can never overtake its packed first occurrence
    # (tests/test_pipeline.py proves this with a duplicate-key hammer
    # differential against the serial path).

    def supports_pipeline(self) -> bool:
        """True when the non-blocking launch/collect split is available:
        native one-pass prep and no Store hooks (a Store needs synchronous
        host calls around every window)."""
        return self._prep_fast is not None and self.store is None

    @staticmethod
    def _staging_buffer(shape, staging, seams) -> np.ndarray:
        """The zeroed i64 staging stack of a group launch: the one parked
        in `staging` under its shape (a pipeline slot's own dict), zeroed
        again, or a new one parked there; an `alloc` span on `seams`."""
        seams("alloc")
        buf = None if staging is None else staging.get(shape)
        if buf is None:
            buf = np.zeros(shape, np.int64)
            if staging is not None:
                staging[shape] = buf
        else:
            buf.fill(0)  # the prep contract: zeroed staging rows
        return buf

    def launch_windows(self, windows, now_ms: Optional[int] = None,
                       staging=None):
        """Dispatch 1..K request-object windows as ONE device launch
        (K > 1 rides the scan kernel) without blocking on the readback.

        `windows` is a list of request lists, each 0 < len <= max_width;
        `staging`, when given, is a dict the engine parks reusable staging
        buffers in (keyed by shape) — the combiner hands each pipeline
        slot its own dict so a buffer is never rewritten while its launch
        may still be reading it. Returns an opaque handle for
        collect_windows, or None when the pipelined path cannot take the
        group at all (nothing mutated, nothing dispatched)."""
        if not self.supports_pipeline():
            return None
        k_req = len(windows)
        if not 0 < k_req <= self._MAX_SCAN:
            return None
        if any(not 0 < len(wk) <= self.max_width for wk in windows):
            return None
        if now_ms is None:
            now_ms = millisecond_now()
        w = max(_bucket_width(len(wk), self.min_width, self.max_width)
                for wk in windows)
        kb = _bucket_pow2(k_req) if k_req > 1 else 1
        prof = self.profiler
        seams = prof.seams()  # host spans, while a capture runs
        buf = self._staging_buffer((kb, 9, w), staging, seams)
        # Segmented group launch. A window whose prep yields LEFTOVERS
        # (duplicate occurrences, gregorian, invalid) CUTS the group: the
        # segment so far dispatches and its tails retire before any later
        # window preps — the ISSUE's pipeline-barrier rule. Otherwise a
        # key pending in window k's tail could be overtaken by its next
        # arrival packed into window k+1 of the same launch, breaking the
        # per-key submission order the serial combiner guarantees. The
        # common serving shape (distinct keys, hits=1) never cuts: one
        # scan dispatch for the whole group.
        meta: List[Optional[tuple]] = [None] * k_req
        tails: List[Optional[list]] = [None] * k_req
        segments = []  # (staged, k_start, m, scanned) in launch order
        led = self.ledger
        if led is not None and not led.enabled:
            led = None
        stashes: List[Optional[tuple]] = [None] * k_req
        k = 0
        while k < k_req:
            seg_start = k
            tq = time.perf_counter_ns() if prof.enabled else 0
            seams("lock_wait")
            with self._lock:
                t0 = time.perf_counter_ns()  # excludes the lock wait
                if tq:
                    prof.lock_wait("launch_windows", t0 - tq)
                seams("prep")
                total = 0
                rounds = 0
                cut = False
                while k < k_req and not cut:
                    wk = windows[k]
                    n0, lane_item, leftover, inject = self._prep_fast(
                        self.directory, wk, buf[k], _GREG_MASK)
                    if n0 == PREP_OVERCOMMIT:
                        self._apply_inject_rows(inject)
                        raise RuntimeError(
                            f"key directory over-committed: "
                            f">{self.capacity} distinct keys in one lookup")
                    if n0 < 0:
                        # defensive — the size preconditions above rule
                        # this out; nothing was committed for THIS window,
                        # so it retires whole through the python tail
                        buf[k][0, :] = -1
                        meta[k] = (0, None,
                                   np.arange(len(wk), dtype=np.int32))
                        k += 1
                        cut = True
                        break
                    self._apply_inject_rows(inject)
                    if n0 == 0:
                        buf[k][0, :] = -1  # prep leaves slot row zeroed
                    meta[k] = (n0, lane_item, leftover)
                    total += n0
                    rounds += 1 if n0 else 0
                    k += 1
                    cut = len(leftover) > 0
                m = k - seg_start
                t1 = time.perf_counter_ns()
                self.stats.stage_ns["prep"] += t1 - t0
                prof.observe("prep", t1 - t0)
                self.stats.requests += total
                self.stats.batches += m
                self.stats.rounds += rounds
                seams("dispatch")
                live = max(meta[kk][0] for kk in range(seg_start, k))
                if m == 1:
                    staged = self._dispatch_staged(buf[seg_start], now_ms,
                                                   live)
                    scanned = False
                else:
                    kb2 = _bucket_pow2(m)
                    if seg_start == 0 and k == k_req and kb2 == kb:
                        # the whole group in one segment: dispatch the
                        # staging stack itself, marking the pow2 pads
                        stack = buf
                        for kk in range(k_req, kb):
                            stack[kk][0, :] = -1
                    elif kb2 == m:
                        stack = buf[seg_start:k]  # contiguous prefix run
                    else:  # rare (a cut left a non-pow2 run): copy-pad
                        with prof.span("alloc"):
                            stack = np.zeros((kb2, 9, w), np.int64)
                            stack[:m] = buf[seg_start:k]
                            stack[m:, 0, :] = -1
                    self.stats.note_scan(rounds, total, len(stack) * w)
                    staged = self._dispatch_scan_staged(stack, now_ms,
                                                        live=live)
                    scanned = True
                td = time.perf_counter_ns()
                self.stats.stage_ns["device"] += td - t1
                prof.observe("dispatch", td - t1)
                if led is not None:
                    # the staging buffer is reused across launches; the
                    # collect side pairs these copies with the readback
                    for kk in range(seg_start, k):
                        stashes[kk] = led.stash_columns(
                            buf[kk], meta[kk][0])
                seams(None)
                if tq:
                    prof.lock_hold("launch_windows",
                                   time.perf_counter_ns() - t0)
            segments.append((staged, seg_start, m, scanned))
            # Leftover tails retire NOW — after this segment's dispatch,
            # before any later window preps — preserving per-key
            # submission order exactly as the serial path does.
            # _slow_window blocks on its own readback; rare path.
            for kk in range(seg_start, k):
                leftover = meta[kk][2]
                if leftover is not None and len(leftover):
                    idxs = leftover.tolist()
                    tails[kk] = self._slow_window(
                        [windows[kk][i] for i in idxs], now_ms,
                        count_batch=False)
        return (segments, windows, meta, tails, stashes)

    def collect_windows(self, handle):
        """Block on a launched group's readbacks (in dispatch order) and
        demux: returns one response list per window, in launch order. Runs
        outside the engine lock — dispatch order is already fixed — so
        later launches proceed while this readback drains."""
        segments, windows, meta, tails, stashes = handle
        led = self.ledger
        if led is not None and not led.enabled:
            led = None
        results: List[Optional[list]] = [None] * len(windows)
        over = 0
        lanes = 0
        t_fetch = 0
        fetched = 0
        prof = self.profiler
        seams = prof.seams()  # host spans, while a capture runs
        t0 = time.perf_counter_ns()
        for staged, seg_start, m, scanned in segments:
            seams("readback")
            tf = time.perf_counter_ns()
            # device sync, this segment
            out, nbytes = self._fetch_staged(staged)
            t_fetch += time.perf_counter_ns() - tf
            seams("demux")
            fetched += nbytes
            for k in range(seg_start, seg_start + m):
                wk = windows[k]
                n0, lane_item, leftover = meta[k]
                responses: List[Optional[RateLimitResp]] = [None] * len(wk)
                if n0:
                    rows = out[k - seg_start] if scanned else out
                    status, limit, remaining, reset = rows[:, :n0].tolist()
                    over += status.count(1)
                    if n0 == len(wk):
                        # nothing was skipped, so lanes are in request
                        # order — build the list directly (the common
                        # serving shape; ~2x less python per decision
                        # than the scatter loop)
                        responses = [
                            RateLimitResp(st, li, re_, rs)
                            for st, li, re_, rs in zip(
                                status, limit, remaining, reset)
                        ]
                    else:
                        for j, i in enumerate(lane_item.tolist()):
                            responses[i] = RateLimitResp(
                                status[j], limit[j], remaining[j], reset[j])
                    lanes += n0
                    if led is not None:
                        led.note_slots_deferred(stashes[k], rows, n0)
                tail = tails[k]
                if tail is not None:
                    for i, resp in zip(leftover.tolist(), tail):
                        responses[i] = resp
                results[k] = responses
        t2 = time.perf_counter_ns()
        seams(None)
        self._obs_device(t_fetch, lanes)
        prof.observe("readback", t_fetch)
        prof.observe("demux", t2 - t0 - t_fetch)
        with self._lock:  # concurrent completers: counters stay exact
            self.stats.over_limit += over
            self.stats.fetched_bytes += fetched
            self.stats.stage_ns["device"] += t_fetch
            self.stats.stage_ns["demux"] += t2 - t0 - t_fetch
        return results

    def launch_noop(self, width: Optional[int] = None):
        """Dispatch one all-padding window (every lane drops — the table
        is untouched) and return its handle: the combiner's depth
        auto-probe times these to pick cycles-in-flight without mutating
        state."""
        w = width or self.min_width
        packed = np.zeros((9, w), np.int64)
        packed[0, :] = -1
        with self._lock:
            return self._dispatch_staged(packed, 0, 0)

    def collect_noop(self, handle) -> None:
        """Block on a launch_noop readback (its bytes are not the link
        counters': no request rode it)."""
        self._fetch_staged(handle)

    def warmup_pipeline(self, max_group: int = 8) -> None:
        """Compile the group-launch scan shapes (pow2 depths <= max_group
        at max_width) the pipelined combiner dispatches under bursts.
        Separate from warmup() so the extra boot cost is opt-in (daemons
        with pipelining on); a cold compile of a scan shape inside a live
        window would stall that window for the whole compile."""
        if not self.supports_pipeline():
            return
        resp = None
        with self._lock:
            k = 2
            while k <= min(max_group, self._MAX_SCAN):
                # different callers' windows, keys at arbitrary lanes: the
                # table-carried programs
                resp = self._warm_scan_depth(self._scans, k, self.max_width)
                k *= 2
            if resp is not None:
                jax.block_until_ready(resp)

    # ------------------------------------------------------- columnar path

    def supports_columnar(self) -> bool:
        """True when the zero-object serving path is available: native
        directory + no Store hooks (stores need per-round host calls)."""
        return self._prep_fast is not None and self.store is None

    # launch_columnar_windows dispatches a group of K windows as ONE
    # program under ONE hold of the engine lock, read back by one copy:
    # what the peerlink pull loop asks before it hands a pull's run of
    # one-window chunks over as a group (service/peerlink.py
    # _columnar_run)
    columnar_group_is_one_launch = True

    def submit_columnar(self, n: int, keys, key_off, name_len, hits, limit,
                        duration, algorithm, behavior, slow_mask: int,
                        now_ms: Optional[int] = None):
        """Dispatch one columnar window: the wire columns (peerlink's
        pls_next_batch layout) go through the GIL-free C prep straight into
        the staging buffer and onto the device — no RateLimitReq objects.

        Returns a handle for complete_columnar, or None when the columnar
        path cannot take the window at all (nothing mutated). The dispatch
        is ASYNC: callers may submit further windows before completing
        earlier ones (≥2 in flight hides device latency; the state chain
        orders them). Items the C pass can't take come back as `leftover`
        indices from complete_columnar — run them through the request-object
        path AFTER this round (per-key sequential order holds because a
        leftover key's first occurrence, if packed, dispatched first)."""
        if not 0 < n <= self.max_width:
            return None
        if now_ms is None:
            now_ms = millisecond_now()
        from gubernator_tpu import native

        w = _bucket_width(n, self.min_width, self.max_width)
        prof = self.profiler
        seams = prof.seams()  # host spans, while a capture runs
        seams("alloc")
        packed = np.zeros((9, w), np.int64)
        tq = time.perf_counter_ns() if prof.enabled else 0
        seams("lock_wait")
        with self._lock:
            t0 = time.perf_counter_ns()  # excludes the lock wait
            if tq:
                prof.lock_wait("submit_columnar", t0 - tq)
            seams("prep")
            n0, lane_item, leftover, inject = native.prep_pack_columnar(
                self.directory, n, keys, key_off, name_len, hits, limit,
                duration, algorithm, behavior, slow_mask, packed)
            if n0 == PREP_OVERCOMMIT:
                self._apply_inject_rows(inject)
                raise RuntimeError(
                    f"key directory over-committed: >{self.capacity} "
                    "distinct keys in one lookup")
            if n0 < 0:
                seams(None)
                if tq:
                    prof.lock_hold("submit_columnar",
                                   time.perf_counter_ns() - t0)
                return None
            t1 = time.perf_counter_ns()
            self.stats.stage_ns["prep"] += t1 - t0
            prof.observe("prep", t1 - t0)
            self.stats.requests += n0
            self.stats.batches += 1
            self._apply_inject_rows(inject)
            handle = None
            stash = None
            if n0:
                self.stats.rounds += 1
                seams("dispatch")
                handle = self._dispatch_staged(packed, now_ms, n0)
                td = time.perf_counter_ns()
                self.stats.stage_ns["device"] += td - t1
                prof.observe("dispatch", td - t1)
                led = self.ledger
                if led is not None and led.enabled:
                    stash = led.stash_columns(packed, n0)
            seams(None)
            if tq:
                prof.lock_hold("submit_columnar",
                               time.perf_counter_ns() - t0)
        return (handle, lane_item, leftover, n0, stash)

    def complete_columnar(self, handle, out_status, out_limit,
                          out_remaining, out_reset) -> np.ndarray:
        """Read back a submitted window and scatter the four response rows
        into the caller's columns at the packed items' positions (runs
        outside the engine lock — dispatch order is already fixed).
        Returns the leftover item indices."""
        staged, lane_item, leftover, n0, stash = handle
        if n0:
            seams = self.profiler.seams()
            seams("readback")
            t0 = time.perf_counter_ns()
            # device sync for THIS window
            rows, nbytes = self._fetch_staged(staged)
            t1 = time.perf_counter_ns()
            seams("demux")
            led = self.ledger
            if led is not None and led.enabled:
                led.note_slots_deferred(stash, rows, n0)
            out_status[lane_item] = rows[0, :n0]
            out_limit[lane_item] = rows[1, :n0]
            out_remaining[lane_item] = rows[2, :n0]
            out_reset[lane_item] = rows[3, :n0]
            over = int(np.count_nonzero(rows[0, :n0] == 1))
            t2 = time.perf_counter_ns()
            seams(None)
            self._obs_device(t1 - t0, n0)
            prof = self.profiler
            prof.observe("readback", t1 - t0)
            prof.observe("demux", t2 - t1)
            with self._lock:  # concurrent completers: counters stay exact
                self.stats.over_limit += over
                self.stats.fetched_bytes += nbytes
                self.stats.stage_ns["device"] += t1 - t0
                self.stats.stage_ns["demux"] += t2 - t1
        return leftover

    # ------------------------------------------- pipelined columnar serving
    # The launch/collect split of the COLUMNAR path: the zero-object twin
    # of launch_windows/collect_windows, driven by the peerlink service
    # (service/peerlink.py _columnar_chunk). Per-key wire order survives
    # by the identical argument: launches serialize under the engine lock
    # (prep order == dispatch order), the device state chain orders the
    # windows' effects, and a window whose prep yields LEFTOVERS cuts the
    # group — the caller must collect and retire them through the
    # request-object path before launching any later sub-window.

    def launch_columnar_windows(self, windows, slow_mask: int,
                                now_ms: Optional[int] = None, staging=None):
        """Dispatch a PREFIX of 1..K columnar sub-windows as ONE device
        launch (K > 1 rides the scan kernel) without blocking on the
        readback.

        `windows` is a list of column tuples (n, keys, key_off, name_len,
        hits, limit, duration, algorithm, behavior) in the peerlink wire
        layout (see submit_columnar), each 0 < n <= max_width; `staging`
        follows the launch_windows contract (one dict per pipeline slot).
        Windows prep in order under ONE lock hold; the first window whose
        prep yields leftovers (duplicates, gregorian, slow-mask demotions,
        invalid) is the LAST window dispatched — the group-cut barrier.

        Returns None when the path cannot take the FIRST window at all
        (nothing mutated — fall back to the object path); otherwise an
        opaque handle for collect_columnar_windows with the cross-backend
        contract: handle[0] is the per-window meta list (len = windows
        CONSUMED, each meta's last element the leftover item indices) and
        handle[1] an over-commit error message or None. On over-commit
        the windows prepped before the failure still dispatch (their
        directory commits must reach the device); the failing window and
        everything after is NOT consumed — the caller error-fills those
        items."""
        if not self.supports_columnar():
            return None
        k_req = len(windows)
        if not 0 < k_req <= self._MAX_SCAN:
            return None
        if any(not 0 < wc[0] <= self.max_width for wc in windows):
            return None
        if now_ms is None:
            now_ms = millisecond_now()
        from gubernator_tpu import native

        # a group launches `max_width` wide, the one width whose group
        # shapes warmup_pipeline compiles (a scan at its windows' own
        # bucket width would compile under the engine lock on a ladder);
        # the host walks a launch's live prefix whatever its width
        w = self.max_width if k_req > 1 else _bucket_width(
            windows[0][0], self.min_width, self.max_width)
        kb = _bucket_pow2(k_req) if k_req > 1 else 1
        prof = self.profiler
        seams = prof.seams()  # host spans, while a capture runs
        buf = self._staging_buffer((kb, 9, w), staging, seams)
        metas: List[tuple] = []
        failed = None
        led = self.ledger
        if led is not None and not led.enabled:
            led = None
        stashes: List[Optional[tuple]] = []
        tq = time.perf_counter_ns() if prof.enabled else 0
        seams("lock_wait")
        with self._lock:
            t0 = time.perf_counter_ns()  # excludes the lock wait
            if tq:
                prof.lock_wait("launch_columnar_windows", t0 - tq)
            seams("prep")
            total = 0
            rounds = 0
            for k, wc in enumerate(windows):
                (n, keys, key_off, name_len, hits, limit, duration,
                 algorithm, behavior) = wc
                n0, lane_item, leftover, inject = native.prep_pack_columnar(
                    self.directory, n, keys, key_off, name_len, hits,
                    limit, duration, algorithm, behavior, slow_mask,
                    buf[k])
                if n0 == PREP_OVERCOMMIT:
                    # earlier windows committed directory state and MUST
                    # still dispatch; this window and the rest are not
                    # consumed (the caller error-fills their items)
                    self._apply_inject_rows(inject)
                    buf[k][0, :] = -1  # partially-written row: all padding
                    failed = (f"key directory over-committed: "
                              f">{self.capacity} distinct keys in one "
                              "lookup")
                    break
                if n0 < 0:
                    if k == 0:
                        seams(None)
                        if tq:
                            prof.lock_hold("launch_columnar_windows",
                                           time.perf_counter_ns() - t0)
                        return None  # nothing mutated: object-path fallback
                    # defensive — the size preconditions rule this out;
                    # nothing committed for THIS window, so it retires
                    # whole through the caller's leftover path, cutting
                    # the group here
                    buf[k][0, :] = -1
                    metas.append((0, None, np.arange(n, dtype=np.int32)))
                    break
                self._apply_inject_rows(inject)
                if n0 == 0:
                    buf[k][0, :] = -1  # prep leaves the slot row zeroed
                metas.append((n0, lane_item, leftover))
                total += n0
                rounds += 1 if n0 else 0
                if len(leftover):
                    break  # group-cut barrier: leftovers retire first
            m = len(metas)
            t1 = time.perf_counter_ns()
            self.stats.stage_ns["prep"] += t1 - t0
            prof.observe("prep", t1 - t0)
            self.stats.requests += total
            self.stats.batches += m
            self.stats.rounds += rounds
            staged = None
            scanned = False
            seams("dispatch" if total else None)
            if total:
                live = max(n0 for n0, _lane_item, _leftover in metas)
                if m == 1:
                    # a group its first window cut is that window alone:
                    # at its own bucket width, as it launches by itself
                    lone = buf[0]
                    wb = _bucket_width(windows[0][0], self.min_width,
                                       self.max_width)
                    if wb < w:
                        lone = np.ascontiguousarray(lone[:, :wb])
                    staged = self._dispatch_staged(lone, now_ms, live)
                else:
                    kb2 = _bucket_pow2(m)
                    stack = buf if kb2 == kb else buf[:kb2]
                    for kk in range(m, kb2):
                        stack[kk][0, :] = -1  # unprepped rows: all padding
                    self.stats.note_scan(rounds, total, kb2 * w)
                    staged = self._dispatch_scan_staged(stack, now_ms,
                                                        live=live)
                    scanned = True
                td = time.perf_counter_ns()
                self.stats.stage_ns["device"] += td - t1
                prof.observe("dispatch", td - t1)
                if led is not None:
                    stashes = [led.stash_columns(buf[kk], metas[kk][0])
                               for kk in range(m)]
                seams(None)
            if tq:
                prof.lock_hold("launch_columnar_windows",
                               time.perf_counter_ns() - t0)
        return (metas, failed, staged, scanned, stashes)

    def collect_columnar_windows(self, handle, outs):
        """Block on a launched columnar group's readback (runs outside the
        engine lock — dispatch order is already fixed) and scatter each
        window's response rows into the caller's column buffers. `outs`
        is one (status, limit, remaining, reset) array 4-tuple per
        CONSUMED window, each sized to that window's item count. Returns
        the per-window leftover index arrays — at most the LAST consumed
        window's is non-empty (the group-cut barrier)."""
        metas, _failed, staged, scanned, stashes = handle
        led = self.ledger
        if led is not None and not led.enabled:
            led = None
        seams = self.profiler.seams()
        seams("readback")
        t0 = time.perf_counter_ns()
        rows_all, nbytes = (self._fetch_staged(staged)
                            if staged is not None else (None, 0))
        t1 = time.perf_counter_ns()
        seams("demux")
        over = 0
        lanes = 0
        leftovers = []
        for k, ((n0, lane_item, leftover), out) in enumerate(
                zip(metas, outs)):
            if n0:
                rows = rows_all[k] if scanned else rows_all
                st, li, re, rs = out
                st[lane_item] = rows[0, :n0]
                li[lane_item] = rows[1, :n0]
                re[lane_item] = rows[2, :n0]
                rs[lane_item] = rows[3, :n0]
                over += int(np.count_nonzero(rows[0, :n0] == 1))
                lanes += n0
                if led is not None and k < len(stashes):
                    led.note_slots_deferred(stashes[k], rows, n0)
            leftovers.append(leftover)
        t2 = time.perf_counter_ns()
        seams(None)
        if lanes:
            self._obs_device(t1 - t0, lanes)
        prof = self.profiler
        prof.observe("readback", t1 - t0)
        prof.observe("demux", t2 - t1)
        with self._lock:  # concurrent completers: counters stay exact
            self.stats.over_limit += over
            self.stats.fetched_bytes += nbytes
            self.stats.stage_ns["device"] += t1 - t0
            self.stats.stage_ns["demux"] += t2 - t1
        return leftovers

    # --------------------------------------------- native lone-request path

    def _apply_inject_rows(self, inject) -> None:
        """Scatter reconciled mirror rows (native lone-path decisions,
        keydir.cpp Mirror) into the device table BEFORE the window whose
        lookup surfaced them. Caller holds the engine lock."""
        if inject is None or len(inject) == 0:
            return
        m = len(inject)
        w = _bucket_width(m, self.min_width, self.max_width)
        pad = w - m
        z = np.zeros(pad, np.int64)

        def col(f):
            return jnp.asarray(np.concatenate([inject[:, f], z]), I64)

        self.state = self._inject(
            self.state,
            jnp.asarray(np.concatenate(
                [inject[:, 0], np.full(pad, -1)]).astype(np.int32), I32),
            col(1).astype(I32), col(2), col(3), col(4), col(5), col(6),
            col(7).astype(I32),
        )

    def decide_native_single(self, req: RateLimitReq,
                             now_ms: int = 0) -> Optional[RateLimitResp]:
        """The native lone-request fast path (VERDICT r2 item 6): decide a
        NO_BATCHING single against the key's directory-resident row mirror
        entirely in C (keydir.cpp decide_one) — no kernel dispatch, no
        engine lock (the KeyDir mutex serializes against batch lookups).
        None = miss (cold/invalidated mirror, masked behavior, store
        attached): take the kernel path, then seed_mirror()."""
        d = self.directory
        if self.store is not None or not hasattr(d, "decide_one"):
            return None
        if int(req.behavior) & _NATIVE_SINGLE_SLOW_MASK:
            return None
        if not req.name or not req.unique_key:
            return None  # the kernel path produces the validation error
        out = d.decide_one(req.hash_key(), req.hits, req.limit,
                           req.duration, int(req.algorithm),
                           int(req.behavior), now_ms)
        if out is None:
            return None
        self.stats.requests += 1
        self.stats.native_singles += 1
        if out[0] == 1:
            self.stats.over_limit += 1
        if self.hot_tracker is not None:
            # native decides bypass the staging dispatchers, so they feed
            # the detector by key instead of by slot row
            self.hot_tracker.feed_key(req.hash_key(), req.hits)
        led = self.ledger
        if led is not None and led.enabled:
            # native decides bypass the staging buffers too: attribute by
            # key directly (a lone request already pays a python wrapper)
            led.record_key(req.hash_key(), req.hits, int(out[0]),
                           int(out[1]), int(out[3]))
        return RateLimitResp(status=int(out[0]), limit=out[1],
                             remaining=out[2], reset_time=out[3])

    def seed_mirror(self, key: str) -> bool:
        """Copy a key's post-window device row into its directory mirror so
        subsequent lone requests decide natively. Called after a lone miss
        took the kernel path (one gather dispatch, amortized across every
        native decision the mirror then serves)."""
        d = self.directory
        if self.store is not None or not hasattr(d, "mirror_seed"):
            return False
        with self._lock:
            slot = d.peek_slot(key)
            if slot < 0:
                return False
            cols = self._gather(self.state, jnp.asarray([slot], I32))
            row = [int(np.asarray(c)[0]) for c in cols]
            if row[0] < 0:
                return False  # vacant row: nothing to mirror
            d.mirror_seed(key, row)
        return True

    # ------------------------------------------------------ hot-key support

    def resolve_slots(self, slots) -> dict:
        """Map slots back to their hash-key strings (models/keyspace.py
        resolve_slots): by index in the native directory, so the cost is
        the slots asked. The hot-key tracker, the cartographer and the
        ledger audit call this from their tickers, never on the serving
        path. Slots without a live directory entry (recycled mid-window)
        are simply absent from the result."""
        return resolve_slots(self.directory, slots)

    def peek_slots(self, keys) -> np.ndarray:
        """The other way round (models/keyspace.py peek_slots): int64 slots
        for a list of hash-keys or a packed arena of them, -1 where the
        directory holds no such key, recency untouched. The ledger audit
        asks this for the keys it tracks, so its cost is theirs and not
        the slots a tick drained."""
        return peek_slots(self.directory, keys)

    def slots_live(self, slots) -> np.ndarray:
        """bool per slot: does the directory hold a key there right now.
        What the ledger audit needs of a slot whose key it does not track:
        lost (`unattributed_hits`) or merely untracked (`key_overflow`),
        and no name."""
        return self.directory.slots_live(slots)

    def device_hit_counts(self, keys) -> dict:
        """Per-key lifetime attempt counters from device row field 7
        (ops/decide.py accumulates every round's requested hits there —
        the durable, on-device view the windowed host tracker samples).
        Debug/test surface: one gather dispatch for the whole key list."""
        d = self.directory
        peek = getattr(d, "peek_slot", None)
        with self._lock:
            pairs = []
            for key in keys:
                if peek is not None:
                    slot = peek(key)
                else:
                    slot = dict(d.items()).get(key, -1)
                if slot >= 0:
                    pairs.append((key, int(slot)))
            if not pairs:
                return {}
            # direct fancy-index fetch: _gather serves the 7 snapshot
            # fields only, and this debug surface needn't be jitted
            rows = fetch_rows(self.state, [s for _, s in pairs])
        return {key: int(rows[i, ROW_HITS])
                for i, (key, _) in enumerate(pairs)}

    def rows_for_keys(self, keys):
        """Point-read the named keys' live rows -> (found_keys,
        rows i64[len(found), 7]) in BucketSnapshot field order — the
        reshard exporter's settle read (service/reshard.py): called under
        its authority fence, so the rows ARE the keys' final state on
        this node. Reconciles the native lone-path mirror first (like
        snapshot_slabs) so fast-path decisions newer than the device
        rows are included; keys that are absent, vacant, or expired are
        simply not in found_keys (the exporter sends them as vacant)."""
        now = millisecond_now()
        d = self.directory
        peek = getattr(d, "peek_slot", None)
        with self._lock:
            if hasattr(d, "mirror_flush"):
                while True:
                    inj = d.mirror_flush()
                    if not len(inj):
                        break
                    self._apply_inject_rows(inj)
            table = None if peek is not None else dict(d.items())
            pairs = []
            for key in keys:
                slot = peek(key) if peek is not None \
                    else table.get(key, -1)
                if slot >= 0:
                    pairs.append((key, int(slot)))
            if not pairs:
                return [], np.zeros((0, 7), np.int64)
            rows = fetch_rows(self.state, [s for _, s in pairs])[:, :7]
        live = (rows[:, 0] >= 0) & (rows[:, 5] >= now)
        found = [key for (key, _), ok in zip(pairs, live) if ok]
        return found, np.ascontiguousarray(rows[live])

    # ------------------------------------------------------- persistence SPI

    def load_snapshot(self, items) -> int:
        """Seed table rows from a Loader (reference: gubernator.go:75-83).

        Consumes any iterable INCREMENTALLY (a streamed Loader at 10M keys
        must not be materialized: the dataclasses alone would cost
        gigabytes) — one max_width chunk of rows exists at a time. The
        engine lock is taken PER CHUNK and never while pulling the source
        iterator: the source may be this engine's own snapshot_stream
        (whose slab fetches take the same non-reentrant lock), and a
        Loader's file/JSON work must not stall serving for the whole
        restore."""
        import itertools

        it_stream = iter(items)
        n = 0
        while True:
            chunk = list(itertools.islice(it_stream, self.max_width))
            if not chunk:
                break
            with self._lock:
                slots, _ = self.directory.lookup([it.key for it in chunk])
                w = _bucket_width(len(chunk), self.min_width, self.max_width)
                pad = w - len(chunk)
                self.state = self._inject(
                    self.state,
                    jnp.asarray(slots + [-1] * pad, I32),
                    jnp.asarray([it.algo for it in chunk] + [0] * pad, I32),
                    jnp.asarray([it.limit for it in chunk] + [0] * pad, I64),
                    jnp.asarray([it.remaining for it in chunk] + [0] * pad, I64),
                    jnp.asarray([it.duration for it in chunk] + [0] * pad, I64),
                    jnp.asarray([it.stamp for it in chunk] + [0] * pad, I64),
                    jnp.asarray([it.expire_at for it in chunk] + [0] * pad, I64),
                    jnp.asarray([it.status for it in chunk] + [0] * pad, I32),
                )
                n += len(chunk)
        return n

    def load_snapshot_slabs(self, slabs) -> int:
        """Binary restore: consume (key_blob, key_offsets i64[m+1],
        rows i64[m, 7]) chunks — snapshot_slabs' shape — with no per-row
        host objects. Same locking contract as load_snapshot (the lock is
        taken per inject chunk, never while pulling the source)."""
        lookup_raw = getattr(self.directory, "lookup_raw", None)
        n = 0
        for blob, off, rows in slabs:
            off = np.asarray(off, np.int64)
            rows = np.asarray(rows, np.int64)
            m = len(off) - 1
            for s in range(0, m, self.max_width):
                e = min(s + self.max_width, m)
                cnt = e - s
                r = rows[s:e]
                with self._lock:
                    if lookup_raw is not None:
                        sub = bytes(blob[off[s]:off[e]])
                        slots, _fresh, _inj = lookup_raw(
                            sub, off[s:e + 1] - off[s])
                        slots = slots.astype(np.int64)
                    else:
                        keys = [blob[off[i]:off[i + 1]].decode("utf-8")
                                for i in range(s, e)]
                        got, _ = self.directory.lookup(keys)
                        slots = np.asarray(got, np.int64)
                    w = _bucket_width(cnt, self.min_width, self.max_width)
                    pad = w - cnt

                    def col(c, dtype):
                        return jnp.asarray(
                            np.pad(c, (0, pad)).astype(dtype))

                    self.state = self._inject(
                        self.state,
                        jnp.asarray(np.pad(slots, (0, pad),
                                           constant_values=-1), I32),
                        col(r[:, 0], np.int32), col(r[:, 1], np.int64),
                        col(r[:, 2], np.int64), col(r[:, 3], np.int64),
                        col(r[:, 4], np.int64), col(r[:, 5], np.int64),
                        col(r[:, 6], np.int32),
                    )
                    n += cnt
        return n

    # ~16 MB of rows per device->host slab: the streamed snapshot's peak
    # host footprint per step, and one compiled slice program total
    _SNAPSHOT_SLAB_ROWS = 1 << 18

    def snapshot_slabs(self, include_expired: bool = False):
        """Stream live rows as binary SLABS (reference: gubernator.go:86-105
        Close/save): yields (key_blob: bytes, key_offsets: i64[m+1],
        rows: i64[m, 7]) chunks with NO per-row host objects — the 10×
        lever over JSONL at production scale (VERDICT r4 item 5). Row
        field order matches BucketSnapshot: algo, limit, remaining,
        duration, stamp, expire_at, status.

        The naive dump at production scale is ruinous twice over: one
        gather dispatch per 8192-key chunk (1,200+ launches at 10M keys)
        and a fully-materialized list of 10M dataclasses (gigabytes of
        host objects). This generator fetches the table in fixed-shape
        row SLABS (one compiled dynamic-slice program, ~16 MB per fetch),
        filters each slab vectorized in numpy, and emits only the live
        rows — peak extra host memory is one slab plus its live subset,
        regardless of table size. Rows stream in slot order.

        Locking: the engine lock is taken PER SLAB, never across a yield
        (a suspended or leaked generator must not wedge the engine — the
        lock is non-reentrant and serving would block forever). Under a
        quiesced engine (shutdown, the normal snapshot moment) the cut is
        exact; under live traffic each slab is internally consistent and
        an entry whose slot was recycled between the directory walk and
        its slab is re-validated (one batch peek per slab) and skipped
        rather than attributed to the wrong key."""
        now = millisecond_now()
        with self._lock:
            if hasattr(self.directory, "mirror_flush"):
                # native lone-path decisions newer than the device rows
                # must reconcile before the gather
                while True:
                    inj = self.directory.mirror_flush()
                    if not len(inj):
                        break
                    self._apply_inject_rows(inj)
            if hasattr(self.directory, "items_raw"):
                blob, off, slots32 = self.directory.items_raw()
            else:  # python-twin directory: build the arena once
                entries = self.directory.items()
                keys_b = [k.encode("utf-8") for k, _ in entries]
                blob = b"".join(keys_b)
                off = np.zeros(len(keys_b) + 1, np.int64)
                if keys_b:
                    np.cumsum([len(b) for b in keys_b], out=off[1:])
                slots32 = np.fromiter((s for _, s in entries), np.int32,
                                      count=len(entries))
        n = len(slots32)
        if n == 0:
            return
        off = np.asarray(off, np.int64)
        lens = off[1:] - off[:-1]
        slots = slots32.astype(np.int64)
        order = np.argsort(slots, kind="stable")
        slots_sorted = slots[order]
        S = min(self._SNAPSHOT_SLAB_ROWS, self.capacity)
        slab_fn = _jit_slab(S)
        batch_peek = getattr(self.directory, "peek_slots_raw", None)
        peek_one = getattr(self.directory, "peek_slot", None)
        blob_arr = np.frombuffer(blob, np.uint8)

        def gather_keys(sel):
            """Vectorized sub-arena build: the selected keys' bytes and
            offsets without a python loop over 256K slices."""
            ln = lens[sel]
            sub_off = np.zeros(sel.size + 1, np.int64)
            np.cumsum(ln, out=sub_off[1:])
            total = int(sub_off[-1])
            # absolute byte positions: each key's start repeated over its
            # length, plus the within-key offset
            pos = np.repeat(off[sel] - sub_off[:-1], ln) + \
                np.arange(total, dtype=np.int64)
            return blob_arr[pos].tobytes(), sub_off

        for a in range(0, self.capacity, S):
            lo, hi = np.searchsorted(slots_sorted, (a, a + S))
            if lo == hi:
                continue  # no directory entries in this row range
            # dynamic_slice CLAMPS an out-of-range start: fetch the
            # final partial slab from capacity-S and index relative to
            # the clamped start (it still covers [a, capacity))
            cs = min(a, self.capacity - S)
            with self._lock:
                slab = host_rows(slab_fn(self.state, cs))
            idx = order[lo:hi]  # original entry index, slot order
            ent_slots = slots_sorted[lo:hi]
            rows = slab[ent_slots - cs]  # [n, 8] in slot order
            live = rows[:, 0] >= 0  # algo < 0 marks a vacant row
            if not include_expired:
                live &= rows[:, 5] >= now
            sel = idx[live]
            if sel.size == 0:
                continue
            ent_sel = ent_slots[live].astype(np.int32)
            sub_blob, sub_off = gather_keys(sel)
            # slot recycled mid-dump: not this key's row anymore
            if batch_peek is not None:
                okm = batch_peek(sub_blob, sub_off) == ent_sel
            elif peek_one is not None:
                okm = np.fromiter(
                    (peek_one(sub_blob[sub_off[k]:sub_off[k + 1]]
                              .decode("utf-8")) == int(s)
                     for k, s in enumerate(ent_sel)), bool, count=sel.size)
            else:
                okm = np.ones(sel.size, bool)
            rows_live = rows[live]
            if not okm.all():
                keep = np.flatnonzero(okm)
                sub_blob, sub_off = gather_keys(sel[keep])
                rows_live = rows_live[keep]
            yield sub_blob, sub_off, np.ascontiguousarray(rows_live[:, :7])

    def snapshot_stream(self, include_expired: bool = False):
        """Stream live rows as BucketSnapshots — the object-level view of
        snapshot_slabs (same walk, same ordering, same consistency
        contract); slab-level consumers (the binary Loader) should use
        snapshot_slabs directly and skip 10M dataclass constructions."""
        for blob, off, rows in self.snapshot_slabs(include_expired):
            for j in range(len(off) - 1):
                r = rows[j]
                yield BucketSnapshot(
                    key=blob[off[j]:off[j + 1]].decode("utf-8"),
                    algo=int(r[0]), limit=int(r[1]), remaining=int(r[2]),
                    duration=int(r[3]), stamp=int(r[4]),
                    expire_at=int(r[5]), status=int(r[6]))

    def snapshot(self, include_expired: bool = False) -> List[BucketSnapshot]:
        """Materialized snapshot_stream (small tables / tests). At
        production scale prefer streaming straight into the Loader."""
        return list(self.snapshot_stream(include_expired))

    def close(self) -> None:
        """Persist via the Loader, mirroring daemon shutdown
        (reference: gubernator.go:86-105). A slab-capable Loader gets the
        binary stream (no per-row objects); plain Loaders keep the
        BucketSnapshot SPI."""
        if self.loader is not None:
            if hasattr(self.loader, "save_slabs"):
                self.loader.save_slabs(self.snapshot_slabs())
            else:
                self.loader.save(self.snapshot_stream())

    # ------------------------------------------------------------- internals

    # Multi-window groups ride one lax.scan dispatch; cap the group so the
    # staging buffer and the set of compiled scan depths stay small. Scan
    # groups are always min_width wide, so warmup() can pre-compile every
    # (depth, width) shape this path can ever dispatch.
    _MAX_SCAN = 32

    def _split_scannable(self, windows):
        """Split the window list into a per-round head and a scannable tail.

        The tail is the maximal run of trailing windows no wider than
        min_width — round sizes only shrink (round k+1's keys are a subset of
        round k's), so the small windows the scan path exists for (duplicate-
        key rounds; a hot-key herd is d one-item rounds) always sit at the
        end. Wide windows keep the per-round path: they are one amortized
        dispatch already, and admitting them would make the scan width
        unbounded (unwarmable shapes, oversized padding).

        A Store keeps the scan path (VERDICT r2 item 5): its hooks batch to
        one read-through before the tail (on the tail's first window — a
        superset of every later round's keys, so it covers the whole tail)
        and one write-through after it with each key's FINAL post-tail row.
        The reference pays one OnChange per hit (algorithms.go:64-68); the
        batched design persists the same end state in one host call per
        window (PARITY #8). The capacity guard keeps a group's up-front
        directory lookups from recycling a slot an earlier window in the
        group already claimed.
        """
        if len(windows) <= 1:
            return windows, []
        split = len(windows)
        while split > 0 and len(windows[split - 1]) <= self.min_width:
            split -= 1
        tail = windows[split:]
        if len(tail) < 2 or sum(len(w) for w in tail) * 4 > self.capacity:
            return windows, []
        return windows[:split], tail

    def _apply_windows_scanned(self, windows, now_ms, responses) -> None:
        """Retire every scannable window in ⌈N/32⌉ dispatches.

        The worst case this exists for is a hot-key thundering herd: d
        duplicates of one key = d rounds, which the per-round path pays d
        full dispatches for — launch overhead plus a host round trip per
        dispatch, while the kernel body is cheap.

        A group whose windows are nested (round k+1's keys among round
        k's: every tail preprocess() makes out of rounds that fit one
        window) is lane-aligned first (_lane_aligned), and its rows ride
        the scan's carry: one row gather and one row scatter a group. Any
        other group (round 0 chunked at max_width, so a later round's key
        lives in a head chunk) keeps the table-carried program, and so
        does every group of a one-width ladder (_carries)."""
        stage = self.stats.stage_ns
        width = self.min_width  # _split_scannable guarantees every window fits
        union = None  # per-key first occurrence across the WHOLE tail
        if self.store is not None and windows:
            # one batched read-through / write-through for the WHOLE tail,
            # over the union of its keys. (The first window alone is NOT a
            # superset: when round 0 chunks at max_width, a later round's
            # keys may live in a HEAD chunk — e.g. rounds [64+2, 4, 4]
            # split the 4 duplicated keys away from tail window 0.)
            seen_keys = {}
            for wk in windows:
                for item in wk:
                    k = item[1].hash_key()
                    if k not in seen_keys:
                        seen_keys[k] = item
            union_items = list(seen_keys.items())  # [(key, item)], in order
            t = time.perf_counter_ns()
            ukeys = [k for k, _ in union_items]
            uslots, ufresh, inj0 = self.directory.lookup_inject(ukeys)
            self._apply_inject_rows(inj0)
            t2 = time.perf_counter_ns()
            stage["lookup"] += t2 - t
            uwork = [it for _, it in union_items]
            ufresh = self._store_read_through(
                uwork, ukeys, uslots, ufresh, now_ms)
            stage["store"] += time.perf_counter_ns() - t2
            union = (uwork, ukeys, uslots)
            # Per-window slot/fresh come from THIS lookup, not re-lookups:
            # a second directory lookup would clear the fresh flag of any
            # first-occurrence key in a LATER tail window (round 0 chunked
            # at max_width), making the kernel treat a recycled slot's
            # stale row as live. `fresh` is consumed by the key's first
            # window; later rounds of the same key see False.
            slot_map = dict(zip(ukeys, uslots))
            fresh_map = {k: f for k, f in zip(ukeys, ufresh) if f}
        for g0 in range(0, len(windows), self._MAX_SCAN):
            group = windows[g0:g0 + self._MAX_SCAN]
            if len(group) == 1:
                # a trailing singleton (e.g. 33 windows -> groups [32, 1])
                # rides the already-warmed single-window program; warmup
                # compiles scan depths {2..32} only
                resolved = None
                if union is not None:
                    wk = group[0]
                    ks = [item[1].hash_key() for item in wk]
                    resolved = ([slot_map[k] for k in ks],
                                [fresh_map.pop(k, False) for k in ks])
                self._apply_round(group[0], now_ms, responses,
                                  skip_store=self.store is not None,
                                  resolved=resolved)
                continue
            k = _bucket_pow2(len(group))
            prof = self.profiler
            seams = prof.seams()  # host spans, while a capture runs
            seams("alloc")
            # the stack holds the group's live lanes alone: pack_window,
            # the converters, the ledger and the demux never see the
            # `width` it launches at
            live = max(len(wk) for wk in group)
            stacked = np.zeros((k, 9, live), np.int64)
            stacked[:, 0, :] = -1  # pad windows are all padding lanes
            t = time.perf_counter_ns()
            seams("prep")
            group_keys = [[item[1].hash_key() for item in wk]
                          for wk in group]
            aligned = self._lane_aligned(group, group_keys) \
                if self._carries(width) else None
            carried = aligned is not None
            if carried:
                group, group_keys = aligned
            host_ns = time.perf_counter_ns() - t
            stage["pack"] += host_ns
            for gi, (wk, keys) in enumerate(zip(group, group_keys)):
                t = time.perf_counter_ns()
                if union is not None:
                    slots = [slot_map[k] for k in keys]
                    fresh = [fresh_map.pop(k, False) for k in keys]
                else:
                    slots, fresh, inj = self.directory.lookup_inject(keys)
                    self._apply_inject_rows(inj)
                t2 = time.perf_counter_ns()
                stage["lookup"] += t2 - t
                pack_window(wk, slots, fresh, live, out=stacked[gi])
                t3 = time.perf_counter_ns()
                stage["pack"] += t3 - t2
                host_ns += t3 - t
            prof.observe("prep", host_ns)
            decided = sum(len(wk) for wk in group)
            self.stats.note_scan(len(group), decided, k * width, carried)
            seams("dispatch")
            t = time.perf_counter_ns()
            staged = self._dispatch_scan_staged(stacked, now_ms, carried,
                                                width=width)
            td = time.perf_counter_ns()
            seams("readback")
            out, nbytes = self._fetch_staged(staged)
            t2 = time.perf_counter_ns()
            seams("demux")
            self.stats.fetched_bytes += nbytes
            stage["device"] += t2 - t
            self._obs_device(t2 - t, decided)
            prof.observe("dispatch", td - t)
            prof.observe("readback", t2 - td)
            led = self.ledger
            for gi, wk in enumerate(group):
                n = len(wk)
                status, limit, remaining, reset = out[gi, :, :n].tolist()
                for j, (i, _r, _ge, _gi) in enumerate(wk):
                    st = status[j]
                    if st == 1:
                        self.stats.over_limit += 1
                    responses[i] = RateLimitResp(
                        status=st, limit=limit[j],
                        remaining=remaining[j], reset_time=reset[j])
                if led is not None and led.enabled:
                    led.note_slots(stacked[gi], out[gi], n)
            demux_ns = time.perf_counter_ns() - t2
            seams(None)
            stage["demux"] += demux_ns
            prof.observe("demux", demux_ns)
        if union is not None:
            # one batched write-through with each key's FINAL post-tail row
            uwork, ukeys, uslots = union
            t = time.perf_counter_ns()
            self._store_write_through(uwork, ukeys, uslots, now_ms)
            stage["store"] += time.perf_counter_ns() - t

    @staticmethod
    def _lane_aligned(group, group_keys):
        """`group`'s windows and their keys reordered so that a key holds
        the same lane in every window it stands in, or None where that
        cannot be had with every window's live lanes a prefix (pack_window,
        the demux and the ledger's note_slots read lanes [0, n), as
        before). Lanes go to the first window's keys by how many windows
        they stay in (a stable sort), which gives nested windows their
        prefixes. A window's items carry their response index, so their
        order inside it is free."""
        stay: Dict[str, int] = {}
        for keys in group_keys:
            for k in keys:
                stay[k] = stay.get(k, 0) + 1
        first = group_keys[0]
        if len(stay) != len(first):
            return None  # a key of a later window has no lane
        order = sorted(range(len(first)), key=lambda j: -stay[first[j]])
        lane = {first[j]: n for n, j in enumerate(order)}
        windows, window_keys = [], []
        for wk, keys in zip(group, group_keys):
            order = sorted(range(len(keys)), key=lambda j: lane[keys[j]])
            if lane[keys[order[-1]]] != len(keys) - 1:
                return None  # this window's lanes are no prefix
            windows.append([wk[j] for j in order])
            window_keys.append([keys[j] for j in order])
        return windows, window_keys

    def _apply_round(self, round_work, now_ms, responses,
                     skip_store: bool = False, resolved=None) -> None:
        """One window, one dispatch. `skip_store` marks a tail singleton
        inside _apply_windows_scanned, whose batched read/write-through
        already covers these keys; `resolved` carries that pass's
        (slots, fresh) so no re-lookup clears a fresh flag. Caller holds
        the engine lock."""
        stage = self.stats.stage_ns
        prof = self.profiler
        seams = prof.seams()  # host spans, while a capture runs
        seams("prep")
        n = len(round_work)
        t = time.perf_counter_ns()
        keys = [item[1].hash_key() for item in round_work]
        if resolved is not None:
            slots, fresh = resolved
        else:
            slots, fresh, inj = self.directory.lookup_inject(keys)
            self._apply_inject_rows(inj)
        lookup_ns = time.perf_counter_ns() - t
        stage["lookup"] += lookup_ns

        use_store = self.store is not None and not skip_store
        if use_store:
            t = time.perf_counter_ns()
            fresh = self._store_read_through(round_work, keys, slots, fresh, now_ms)
            stage["store"] += time.perf_counter_ns() - t

        w = _bucket_width(n, self.min_width, self.max_width)
        # one staging buffer up, one back: off-chip round trips are the
        # serving path's dominant cost, so the window crosses exactly twice
        t = time.perf_counter_ns()
        packed = pack_window(round_work, slots, fresh, w)
        t2 = time.perf_counter_ns()
        stage["pack"] += t2 - t
        # lookup + pack are host prep in the profiler's cycle taxonomy
        prof.observe("prep", lookup_ns + (t2 - t))
        seams("dispatch")
        staged = self._dispatch_staged(packed, now_ms, n)
        td = time.perf_counter_ns()
        seams("readback")
        out, nbytes = self._fetch_staged(staged)
        t3 = time.perf_counter_ns()
        seams("demux")
        self.stats.fetched_bytes += nbytes
        stage["device"] += t3 - t2
        self._obs_device(t3 - t2, n)
        prof.observe("dispatch", td - t2)
        prof.observe("readback", t3 - td)

        # one C-level tolist beats four per-element int() casts per lane
        status, limit, remaining, reset = out[:, :n].tolist()
        for j, (i, _r, _ge, _gi) in enumerate(round_work):
            st = status[j]
            if st == 1:
                self.stats.over_limit += 1
            responses[i] = RateLimitResp(
                status=st, limit=limit[j], remaining=remaining[j],
                reset_time=reset[j])
        demux_ns = time.perf_counter_ns() - t3
        seams(None)
        stage["demux"] += demux_ns
        prof.observe("demux", demux_ns)
        led = self.ledger
        if led is not None and led.enabled:
            led.note_slots(packed, out, n)

        if use_store:
            t = time.perf_counter_ns()
            self._store_write_through(round_work, keys, slots, now_ms)
            stage["store"] += time.perf_counter_ns() - t

    def _store_read_through(self, round_work, keys, slots, fresh, now_ms):
        """Consult the store for rows the table can't serve
        (reference: algorithms.go:26-33). Caller holds the engine lock."""
        slot_arr = jnp.asarray(slots, I32)
        algo_c, _, _, _, _, exp_c, _ = (np.asarray(c) for c in
                                        self._gather(self.state, slot_arr))
        inj = {"slot": [], "algo": [], "limit": [], "remaining": [],
               "duration": [], "stamp": [], "expire_at": [], "status": []}
        fresh = list(fresh)
        for j, (i, r, _ge, _gi) in enumerate(round_work):
            live = not fresh[j] and int(algo_c[j]) >= 0 and now_ms <= int(exp_c[j])
            if live and int(algo_c[j]) != int(r.algorithm):
                # algorithm switch discards the old bucket everywhere
                # (reference: algorithms.go:54-62)
                self.store.remove(keys[j])
                live = False
            if live:
                continue
            item = self.store.get(r)
            if item is None:
                continue
            inj["slot"].append(slots[j])
            inj["algo"].append(item.algo)
            inj["limit"].append(item.limit)
            inj["remaining"].append(item.remaining)
            inj["duration"].append(item.duration)
            inj["stamp"].append(item.stamp)
            inj["expire_at"].append(item.expire_at)
            inj["status"].append(item.status)
            fresh[j] = False  # the injected row is now live
        if inj["slot"]:
            m = len(inj["slot"])
            w = _bucket_width(m, self.min_width, self.max_width)
            pad = w - m
            self.state = self._inject(
                self.state,
                jnp.asarray(inj["slot"] + [-1] * pad, I32),
                jnp.asarray(inj["algo"] + [0] * pad, I32),
                jnp.asarray(inj["limit"] + [0] * pad, I64),
                jnp.asarray(inj["remaining"] + [0] * pad, I64),
                jnp.asarray(inj["duration"] + [0] * pad, I64),
                jnp.asarray(inj["stamp"] + [0] * pad, I64),
                jnp.asarray(inj["expire_at"] + [0] * pad, I64),
                jnp.asarray(inj["status"] + [0] * pad, I32),
            )
        return fresh

    def _store_write_through(self, round_work, keys, slots, now_ms):
        """Report post-decision rows (reference: algorithms.go:64-68,175-177);
        discarded buckets get `remove` (reference: algorithms.go:37-39,57-59).
        Caller holds the engine lock."""
        slot_arr = jnp.asarray(slots, I32)
        cols = [np.asarray(c) for c in self._gather(self.state, slot_arr)]
        for j, (i, r, _ge, _gi) in enumerate(round_work):
            algo = int(cols[0][j])
            if algo < 0:
                # token RESET_REMAINING cleared the row
                self.store.remove(keys[j])
                self.directory.drop(keys[j])
                continue
            self.store.on_change(r, BucketSnapshot(
                key=keys[j], algo=algo, limit=int(cols[1][j]),
                remaining=int(cols[2][j]), duration=int(cols[3][j]),
                stamp=int(cols[4][j]), expire_at=int(cols[5][j]),
                status=int(cols[6][j])))

"""Mesh-sharded rate-limit engine: the multi-chip authoritative state tier.

Single-host view of the distributed design (SURVEY.md §2.2): the key table is
sharded over a ("region", "shard") mesh; every key has exactly one owner chip
(reference's owner-peer model, architecture.md:13-17) and one batch window
becomes one `shard_map`ped kernel launch where each chip applies the lanes
routed to it. The reference's non-owner -> owner gRPC forwarding
(peer_client.go:215-319) is replaced by host-side lane routing into the
[R, S, W] batch; its GLOBAL gRPC pipelines are replaced by the psum step in
parallel/global_sync.py.

Behavior=GLOBAL here (reference: gubernator.go:226-247):
- requests are answered from the replicated host-side mirror (the owner's
  last broadcast), with local hit deltas accumulated for the next sync;
- a key's FIRST touch (mirror miss) goes through the authoritative kernel
  synchronously and its hits are NOT queued — slightly stricter than the
  reference, which both queues the hit and processes it as-if-owner
  (double-counting one window's hits, gubernator.go:227-246);
- between syncs the local mirror's `remaining` is optimistically decremented
  by locally-queued hits — stricter than the reference, which returns the
  cached broadcast unmodified (gubernator.go:232-240) and so admits
  unbounded hits per peer per sync window; each broadcast overwrites the
  optimistic copy with the authoritative psum result.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from gubernator_tpu.obs import witness
from gubernator_tpu.obs.profile import Profiler
from gubernator_tpu.models.keyspace import KeyDirectory
from gubernator_tpu.models.prep import (
    WorkItem,
    bucket_pow2 as _bucket_pow2,
    bucket_width,
    preprocess,
)
from gubernator_tpu.ops.decide import (
    ROW_ALGO,
    ROW_DURATION,
    ROW_EXPIRE,
    ROW_LIMIT,
    ROW_REMAINING,
    ROW_STAMP,
    ROW_STATUS,
    TableState,
    decide_packed,
    decide_packed_lean,
    decide_scan_packed,
    decide_scan_packed_lean,
    host_rows,
    host_words,
    lean_capacity_ok,
    lean_window,
    load_rows,
    staging_policy,
    store_rows,
    widen_compact_out,
    pack_window,
)
from gubernator_tpu.parallel.global_sync import (
    GlobalConfig,
    GlobalMirror,
    make_global_sync,
)
from gubernator_tpu.parallel.mesh import (
    REGION_AXIS,
    SHARD_AXIS,
    MeshPlan,
    make_mesh,
    make_sharded_table,
    shard_of_key,
)
from gubernator_tpu.types import (
    Behavior,
    RateLimitReq,
    RateLimitResp,
    Status,
    has_behavior,
)
from gubernator_tpu.utils.interval import millisecond_now
from gubernator_tpu.utils.platform import release_compile_memory

from gubernator_tpu import native
from gubernator_tpu.native import PREP_OVERCOMMIT

# lanes the sharded native fast path must hand to the python pipeline:
# gregorian (host calendar math) and GLOBAL (mirror/psum tier)
_SLOW_MASK = int(Behavior.DURATION_IS_GREGORIAN) | int(Behavior.GLOBAL)


def make_decide_sharded(plan: MeshPlan, donate: bool = False):
    """Compile the batched decision kernel over the plan's mesh.

    fn(state [R,S,C], packed i64[R,S,9,W], now) -> (state, out i64[R,S,4,W]);
    each chip applies its own lane slice to its own table shard — no
    cross-chip traffic at all on the normal (non-GLOBAL) path, mirroring the
    reference's owner-local mutation. Requests ride ONE staging buffer up
    and one back (see ops/decide.py decide_packed; the host-side packer is
    ShardedEngine._apply_round — keep row orders in sync).
    """
    spec_state = P(REGION_AXIS, SHARD_AXIS, None, None)
    spec_io = P(REGION_AXIS, SHARD_AXIS, None, None)

    def _step(state: TableState, packed: jax.Array, now: jax.Array):
        local_state = state.reshape(state.shape[-2:])
        new_state, out = decide_packed(
            local_state, packed.reshape(packed.shape[-2:]), now
        )
        return (
            new_state.reshape((1, 1) + new_state.shape),
            out.reshape(1, 1, *out.shape),
        )

    mapped = jax.shard_map(
        _step, mesh=plan.mesh,
        in_specs=(spec_state, spec_io, P()),
        out_specs=(spec_state, spec_io),
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def make_decide_sharded_scan(plan: MeshPlan, donate: bool = False):
    """Scan-coalesced variant of make_decide_sharded.

    fn(state [R,S,C], packed i64[R,S,K,9,W], now) -> (state, out
    i64[R,S,K,4,W]): each chip retires K windows over its own shard in ONE
    dispatch — `lax.scan` runs *inside* the shard_map, so the K windows cost
    one launch instead of K (launch overhead dominates; see
    ops/decide.py decide_scan_packed). Window k+1 observes window k's
    writes shard-locally, which is exactly the duplicate-key *rounds*
    ordering the engine needs.
    """
    spec_state = P(REGION_AXIS, SHARD_AXIS, None, None)
    spec_io = P(REGION_AXIS, SHARD_AXIS, None, None, None)

    def _step(state: TableState, packed_k: jax.Array, now: jax.Array):
        local_state = state.reshape(state.shape[-2:])
        new_state, out = decide_scan_packed(
            local_state, packed_k.reshape(packed_k.shape[-3:]), now
        )
        return (
            new_state.reshape((1, 1) + new_state.shape),
            out.reshape(1, 1, *out.shape),
        )

    mapped = jax.shard_map(
        _step, mesh=plan.mesh,
        in_specs=(spec_state, spec_io, P()),
        out_specs=(spec_state, spec_io),
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def make_decide_sharded_lean(plan: MeshPlan, donate: bool = False):
    """Lean-lane variant of make_decide_sharded (r5): fn(state [R,S,C,8],
    lanes i32[R,S,W], cfg i64[128,4], now) -> (state, out i32[R,S,4,W]).

    The staging buffer drops from 72 B to 4 B per lane — on a multi-chip
    host the host->device transfer is the window's dominant byte cost,
    and the lean lane cuts it 18x for the dominant serving shape
    (hits=1, few configs; ops/decide.py "lean"). Slots are shard-LOCAL
    (each chip's lane slice indexes its own table shard, same as the
    wide path); the config table is fleet-global and replicated."""
    spec_state = P(REGION_AXIS, SHARD_AXIS, None, None)
    spec_lanes = P(REGION_AXIS, SHARD_AXIS, None)
    spec_out = P(REGION_AXIS, SHARD_AXIS, None, None)

    def _step(state: TableState, lanes: jax.Array, cfg: jax.Array,
              now: jax.Array):
        local_state = state.reshape(state.shape[-2:])
        new_state, out = decide_packed_lean(
            local_state, lanes.reshape(lanes.shape[-1:]), cfg, now)
        return (
            new_state.reshape((1, 1) + new_state.shape),
            out.reshape(1, 1, *out.shape),
        )

    mapped = jax.shard_map(
        _step, mesh=plan.mesh,
        in_specs=(spec_state, spec_lanes, P(), P()),
        out_specs=(spec_state, spec_out),
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def make_decide_sharded_scan_lean(plan: MeshPlan, donate: bool = False):
    """Scan-coalesced lean variant: fn(state, lanes i32[R,S,K,W], cfg,
    now) -> (state, out i32[R,S,K,4,W]) — K lean windows per shard in one
    dispatch (see make_decide_sharded_scan for the rounds ordering)."""
    spec_state = P(REGION_AXIS, SHARD_AXIS, None, None)
    spec_lanes = P(REGION_AXIS, SHARD_AXIS, None, None)
    spec_out = P(REGION_AXIS, SHARD_AXIS, None, None, None)

    def _step(state: TableState, lanes_k: jax.Array, cfg: jax.Array,
              now: jax.Array):
        local_state = state.reshape(state.shape[-2:])
        new_state, out = decide_scan_packed_lean(
            local_state, lanes_k.reshape(lanes_k.shape[-2:]), cfg, now)
        return (
            new_state.reshape((1, 1) + new_state.shape),
            out.reshape(1, 1, *out.shape),
        )

    mapped = jax.shard_map(
        _step, mesh=plan.mesh,
        in_specs=(spec_state, spec_lanes, P(), P()),
        out_specs=(spec_state, spec_out),
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def make_gather_sharded(plan: MeshPlan):
    """Row gather over the mesh, for the Store hooks and snapshot deltas.

    fn(state [R,S,C], slot i32[R,S,W]) -> rows i64[R,S,7,W]: each chip reads
    its own slot lanes (lanes with slot -1 return garbage the caller must
    mask on `algo < 0` / its own bookkeeping). One staging buffer back, like
    the decide kernels — the host tier's cost is off-chip round trips.
    Row order is TableState field order; make_inject_sharded mirrors it.
    """
    spec_state = P(REGION_AXIS, SHARD_AXIS, None, None)
    spec_slot = P(REGION_AXIS, SHARD_AXIS, None)
    spec_out = P(REGION_AXIS, SHARD_AXIS, None, None)

    def _step(state: TableState, slot: jax.Array):
        local = state.reshape(state.shape[-2:])
        g = jnp.maximum(slot.reshape(slot.shape[-1:]), 0)
        # row fields 0..6 ARE the output row order (pad field dropped)
        rows = load_rows(local, g)[:, :7].T
        return rows.reshape(1, 1, *rows.shape)

    mapped = jax.shard_map(
        _step, mesh=plan.mesh,
        in_specs=(spec_state, spec_slot), out_specs=spec_out,
    )
    return jax.jit(mapped)


def make_inject_sharded(plan: MeshPlan, donate: bool = False):
    """Row scatter over the mesh: the Store read-through's injection path.

    fn(state [R,S,C], slot i32[R,S,W], rows i64[R,S,7,W]) -> state; lanes
    with slot -1 are dropped. Mirrors models/engine.py _inject_rows for the
    single-table engine (reference: algorithms.go:26-33 read-through)."""
    spec_state = P(REGION_AXIS, SHARD_AXIS, None, None)
    spec_slot = P(REGION_AXIS, SHARD_AXIS, None)
    spec_rows = P(REGION_AXIS, SHARD_AXIS, None, None)

    def _step(state: TableState, slot: jax.Array, rows: jax.Array):
        local = state.reshape(state.shape[-2:])
        r = rows.reshape(rows.shape[-2:])  # [7, W], row field order
        w8 = jnp.concatenate(
            [r.T, jnp.zeros((r.shape[1], 1), r.dtype)], axis=1)
        new = store_rows(local, slot.reshape(slot.shape[-1:]), w8)
        return new.reshape((1, 1) + new.shape)

    mapped = jax.shard_map(
        _step, mesh=plan.mesh,
        in_specs=(spec_state, spec_slot, spec_rows), out_specs=spec_state,
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


class _GlobalEntry:
    """Host record for one registered global key."""

    __slots__ = ("gidx", "owner", "req", "seen", "last_ms")

    def __init__(self, gidx: int, owner: int, now_ms: int):
        self.gidx = gidx
        self.owner = owner
        self.req: Optional[RateLimitReq] = None
        self.seen = False  # at least one broadcast has populated the mirror
        self.last_ms = now_ms  # last request touch (LRU / idle eviction)


class ShardedEngine:
    """Authoritative rate-limit state sharded over a device mesh."""

    def __init__(
        self,
        mesh=None,
        n_shards: Optional[int] = None,
        n_regions: int = 1,
        capacity_per_shard: int = 1 << 17,
        global_capacity: int = 1024,
        min_width: int = 64,
        max_width: int = 8192,
        donate: Optional[bool] = None,
        loader=None,
        store=None,
        global_idle_ms: int = 60_000,
    ):
        if mesh is None:
            mesh = make_mesh(n_shards=n_shards, n_regions=n_regions)
        self.plan = MeshPlan(mesh=mesh, capacity_per_shard=capacity_per_shard)
        if donate is None:
            from gubernator_tpu.utils.platform import donation_supported

            donate = donation_supported()
        self.donate = donate
        self.state = make_sharded_table(self.plan)
        self._decide = make_decide_sharded(self.plan, donate=donate)
        self._decide_scan = make_decide_sharded_scan(self.plan, donate=donate)
        self._decide_lean = make_decide_sharded_lean(self.plan,
                                                     donate=donate)
        self._decide_scan_lean = make_decide_sharded_scan_lean(
            self.plan, donate=donate)
        # staging policy, same contract as models/engine.py: auto ships
        # eligible windows on the 4 B/lane lean wire; wide pins i64[9]
        self._staging = staging_policy()
        self._lean_ok = lean_capacity_ok(capacity_per_shard)
        self._sync = make_global_sync(self.plan, donate=donate)
        self.store = store
        if store is not None:
            self._gather = make_gather_sharded(self.plan)
            self._inject = make_inject_sharded(self.plan, donate=donate)
        from gubernator_tpu.native import make_key_directory

        self.directories = [
            make_key_directory(capacity_per_shard)
            for _ in range(self.plan.n_owners)
        ]
        # native one-pass window prep + owner routing (see Engine._fast_window)
        self._prep_fast = (
            native.prep_route_sharded
            if all(isinstance(d, native.NativeKeyDirectory)
                   for d in self.directories)
            else None
        )
        self.min_width = min_width
        self.max_width = min(max_width, capacity_per_shard)
        self._lock = witness.make_lock("sharded.engine")
        self.loader = loader

        # ---- GLOBAL-behavior host state --------------------------------
        # The registry is an LRU within global_capacity (the reference routes
        # GLOBAL keys through its general 50k LRU, cache.go:82-84): gidx
        # slots are recycled through a free list, idle entries are swept
        # after each sync, and when the registry is full the
        # least-recently-touched zero-delta entry is evicted to make room.
        # Only when every slot still has unsynced hits does a NEW global key
        # fall back to the authoritative path (counted, never permanent).
        self.global_capacity = global_capacity
        self.global_idle_ms = global_idle_ms
        # recency-ordered (oldest first): touches move_to_end, so the LRU
        # victim is the first zero-delta entry in iteration order
        self._globals: "OrderedDict[str, _GlobalEntry]" = OrderedDict()
        self._gfree: List[int] = []  # recycled gidx slots
        self._gnext = 0  # high-water mark of allocated gidx
        self._gdelta = np.zeros((global_capacity,), np.int64)  # local hits
        self._mirror = GlobalMirror(  # host copy of last broadcast
            status=np.zeros((global_capacity,), np.int32),
            limit=np.zeros((global_capacity,), np.int64),
            remaining=np.zeros((global_capacity,), np.int64),
            reset_time=np.zeros((global_capacity,), np.int64),
        )
        self.stats = {
            "requests": 0,
            "batches": 0,
            "rounds": 0,
            "over_limit": 0,
            "errors": 0,
            "global_hits_queued": 0,
            "global_syncs": 0,
            "global_mirror_answers": 0,
            "global_evictions": 0,
            "global_registry_fallbacks": 0,
            "lean_windows": 0,  # windows shipped on the 4 B/lane wire
            # Σ over windows of the fullest shard's lanes: that shard sets
            # the launch's padded width, so lanes_max x shards / requests
            # is how unevenly the windows split (1.0 = evenly)
            "lanes_max": 0,
            # the link, counted in the funnels every launch and fetch go
            # through (_launch_mesh, _fetch_mesh), as models/engine.py
            # EngineStats counts its own: nbytes of the host arrays handed
            # to the program, nbytes copied back
            "staged_bytes": 0,
            "fetched_bytes": 0,
        }
        # per-stage wall clocks, same contract as models/engine.py
        # EngineStats (exposed as engine_stage_seconds_total in /metrics).
        # On the native paths prep_ns is the C route + lookup alone and
        # pack_ns the Python pack; together they are the `prep` phase.
        from gubernator_tpu.models.engine import EngineStats

        for s in EngineStats.STAGES:
            self.stats[f"{s}_ns"] = 0
        # the cycle profiler (obs/profile.py), stamped where Engine stamps
        # its own and under the same phase names: the Instance, the
        # combiner, /metrics and the capture's host spans take it from here
        self.profiler = Profiler()

        if loader is not None:
            self.load_snapshot(loader.load())
        # boot line + /v1/debug/vars: one entry per device the table's
        # shards actually sit on (utils/platform.py)
        from gubernator_tpu.utils.platform import device_facts

        self.device = device_facts(
            self, "native" if self._prep_fast is not None else "python")

    # ------------------------------------------------------------------ API

    def warmup(self) -> None:
        """Compile the mesh kernel for every width bucket and scan shape up
        front, so no serve-time request pays seconds of XLA compile (see
        Engine.warmup; daemons call this before reporting ready)."""
        R, S = self.plan.n_regions, self.plan.n_shards
        widths = []
        w = self.min_width
        while w < self.max_width:
            widths.append(w)
            w *= 2
        widths.append(self.max_width)
        resp = None
        with self._lock:
            lean_warm = self._staging != "wide" and self._lean_ok
            for width in widths:
                packed = np.zeros((R, S, 9, width), np.int64)
                packed[:, :, 0, :] = -1
                self.state, resp = self._decide(self.state, packed, 0)
                if lean_warm:  # auto mode serves either wire format
                    ln = lean_window(packed, self.plan.capacity_per_shard)
                    self.state, resp = self._decide_lean(
                        self.state, jnp.asarray(ln[0]),
                        jnp.asarray(ln[1]), 0)
                release_compile_memory()
            k = 2
            while k <= self._MAX_SCAN:
                packed = np.zeros((R, S, k, 9, self.min_width), np.int64)
                packed[:, :, :, 0, :] = -1
                self.state, resp = self._decide_scan(self.state, packed, 0)
                if lean_warm:
                    ln = lean_window(packed, self.plan.capacity_per_shard)
                    self.state, resp = self._decide_scan_lean(
                        self.state, jnp.asarray(ln[0]),
                        jnp.asarray(ln[1]), 0)
                release_compile_memory()
                k *= 2
            if self.store is not None:
                # the Store path adds two gathers + an inject per window
                # (_apply_round_store) and a gather per global sync
                # (_store_write_global, whose width ladder is capped by
                # global_capacity rather than max_width)
                gather_widths = set(widths)
                w = self.min_width
                while w < self.global_capacity:
                    gather_widths.add(w)
                    w *= 2
                gather_widths.add(
                    bucket_width(self.global_capacity, self.min_width,
                                 self.global_capacity))
                for width in sorted(gather_widths):
                    slotmat = np.full((R, S, width), -1, np.int32)
                    resp = self._gather(self.state, slotmat)
                    if width in widths:
                        self.state = self._inject(
                            self.state, slotmat,
                            np.zeros((R, S, 7, width), np.int64))
            # the GLOBAL sync kernel is one fixed-shape program; an
            # explicitly empty config + zero delta exercises it as a
            # guaranteed no-op — live host state (registered globals,
            # pending _gdelta) must NOT feed a warmup, or re-warming a
            # serving engine would apply queued hits here and again at the
            # next real sync
            G = self.global_capacity
            z32 = np.zeros((G,), np.int32)
            z64 = np.zeros((G,), np.int64)
            empty_cfg = GlobalConfig(
                slot=jnp.asarray(np.full((G,), -1, np.int32)),
                owner=jnp.asarray(z32), limit=jnp.asarray(z64),
                duration=jnp.asarray(z64), algorithm=jnp.asarray(z32),
                behavior=jnp.asarray(z32), greg_expire=jnp.asarray(z64),
                greg_interval=jnp.asarray(z64),
                fresh=jnp.asarray(np.zeros((G,), np.bool_)))
            self.state, _, _ = self._sync(
                self.state, np.zeros((R, S, G), np.int64), empty_cfg, 0)
            if resp is not None:
                jax.block_until_ready(resp)

    def owner_of(self, key: str) -> int:
        return shard_of_key(key, self.plan.n_owners)

    # ------------------------------------------------------- persistence SPI

    def snapshot(self, include_expired: bool = False):
        """Dump live rows across every shard (single-process meshes; a
        multi-host group snapshots per host, each daemon owning its local
        shards). Mirrors Engine.snapshot (reference: gubernator.go:86-105)."""
        from gubernator_tpu.store import BucketSnapshot
        from gubernator_tpu.utils.interval import millisecond_now

        out = []
        now = millisecond_now()
        with self._lock:
            tbl = host_rows(self.state)  # [R, S, C, 8]
            for owner, directory in enumerate(self.directories):
                r_, s_ = self.plan.owner_coords(owner)
                for key, slot in directory.items():
                    row = tbl[r_, s_, slot]
                    algo = int(row[ROW_ALGO])
                    expire = int(row[ROW_EXPIRE])
                    if algo < 0:
                        continue
                    if not include_expired and now > expire:
                        continue
                    out.append(BucketSnapshot(
                        key=key, algo=algo,
                        limit=int(row[ROW_LIMIT]),
                        remaining=int(row[ROW_REMAINING]),
                        duration=int(row[ROW_DURATION]),
                        stamp=int(row[ROW_STAMP]),
                        expire_at=expire,
                        status=int(row[ROW_STATUS])))
        return out

    def load_snapshot(self, items) -> int:
        """Seed table rows from a Loader at boot (boot-time only: columns
        round-trip through the host). Reference: gubernator.go:75-83."""
        items = list(items)
        if not items:
            return 0
        with self._lock:
            # writable host copy [R, S, C, 8]
            tbl = host_rows(self.state).copy()
            n = 0
            by_owner: Dict[int, list] = {}
            for it in items:
                by_owner.setdefault(self.owner_of(it.key), []).append(it)
            for owner, rows in by_owner.items():
                r_, s_ = self.plan.owner_coords(owner)
                # chunked lookups: a snapshot larger than the (possibly
                # resized-down) shard degrades via LRU eviction instead of
                # tripping the directory's over-commit guard, mirroring
                # Engine.load_snapshot
                for start in range(0, len(rows), self.max_width):
                    chunk = rows[start:start + self.max_width]
                    slots, _ = self.directories[owner].lookup(
                        [it.key for it in chunk])
                    for it, slot in zip(chunk, slots):
                        tbl[r_, s_, slot, :7] = (
                            it.algo, it.limit, it.remaining, it.duration,
                            it.stamp, it.expire_at, it.status)
                        n += 1
            self.state = jax.device_put(
                host_words(tbl), self.plan.state_sharding())
        return n

    def close(self) -> None:
        """Persist via the Loader, mirroring daemon shutdown
        (reference: gubernator.go:86-105). Pending GLOBAL hit deltas are
        flushed through one last sync first so the persisted rows — the
        Loader snapshot AND the Store's write-through copies — reflect every
        admitted hit, not just the last broadcast."""
        if ((self.loader is not None or self.store is not None)
                and self.global_pending_hits()):
            self.global_sync()
        if self.loader is not None:
            self.loader.save(self.snapshot())

    def get_rate_limits(
        self, requests: Sequence[RateLimitReq], now_ms: Optional[int] = None
    ) -> List[RateLimitResp]:
        if now_ms is None:
            now_ms = millisecond_now()
        if (self._prep_fast is not None and self.store is None
                and 0 < len(requests) <= self.max_width):
            fast = self._fast_window(requests, now_ms)
            if fast is not None:
                return fast
        return self._slow_window(requests, now_ms)

    def _fast_window(self, requests, now_ms) -> Optional[List[RateLimitResp]]:
        """Native one-pass window: validate + first-occurrence split + owner
        routing + per-owner directory lookup in one C call
        (native/keydir.cpp keydir_prep_route_sharded). Leftover lanes —
        invalid, gregorian, GLOBAL, duplicate occurrences — run through the
        python pipeline AFTER this round (same per-key order contract as
        Engine._fast_window, and its stamps: lock_wait at site
        `fast_window`, prep, dispatch, readback and demux, all under the
        lock)."""
        seams, tq = self._stamp_lock_wait()
        with self._lock:
            t0 = self._stamp_lock_held("fast_window", tq, seams)
            n0, cols, lane_item, owner_count, leftover = self._prep_fast(
                self.directories, requests, _SLOW_MASK)
            if n0 < 0:
                seams(None)
                if n0 == PREP_OVERCOMMIT:
                    self._raise_overcommit()
                if tq:
                    self.profiler.lock_hold(
                        "fast_window", time.perf_counter_ns() - t0)
                return None
            out, placed = self._pack_and_decide(
                n0, cols, lane_item, owner_count, now_ms, t0, seams)
            responses: List[Optional[RateLimitResp]] = [None] * len(requests)
            if n0:
                self._book_collect(
                    *self._collect(out, placed, self._demux, responses))
            if tq:
                self.profiler.lock_hold(
                    "fast_window", time.perf_counter_ns() - t0)
        if len(leftover):
            idxs = leftover.tolist()
            tail = self._slow_window(
                [requests[i] for i in idxs], now_ms, count_batch=False)
            for i, resp in zip(idxs, tail):
                responses[i] = resp
        return responses  # type: ignore[return-value]

    # ------------------------------------------------------- columnar path

    def supports_columnar(self) -> bool:
        """True when the zero-object columnar serving path is available
        (Engine.supports_columnar's mesh twin; nothing is stamped here)."""
        return self._prep_fast is not None and self.store is None

    # launch_columnar_windows launches a window at a time here (one
    # shard_map call and one lock hold each): a group is as many launches
    # as lock-step makes, left in flight, so the peerlink pull loop keeps
    # a pull's one-window chunks lock-step on this backend
    columnar_group_is_one_launch = False

    def submit_columnar(self, n: int, keys, key_off, name_len, hits, limit,
                        duration, algorithm, behavior, slow_mask: int,
                        now_ms: Optional[int] = None):
        """Dispatch one columnar window over the mesh: wire columns route
        to owner shards in one GIL-free C pass
        (native/keydir.cpp keydir_prep_route_columnar) and decide in one
        shard_map'ped launch. Same contract as Engine.submit_columnar —
        the peerlink server drives either backend through it — and the
        same stamps: lock_wait at site `submit_columnar`, prep (route +
        pack here, lookup + pack there) and dispatch."""
        if not 0 < n <= self.max_width:
            return None
        if now_ms is None:
            now_ms = millisecond_now()
        seams, tq = self._stamp_lock_wait()
        with self._lock:
            t0 = self._stamp_lock_held("submit_columnar", tq, seams)
            n0, cols, lane_item, owner_count, leftover = \
                native.prep_route_columnar(
                    self.directories, n, keys, key_off, name_len, hits,
                    limit, duration, algorithm, behavior,
                    slow_mask | _SLOW_MASK)
            if n0 < 0:
                seams(None)
                if n0 == PREP_OVERCOMMIT:
                    self._raise_overcommit()
                if tq:
                    self.profiler.lock_hold(
                        "submit_columnar", time.perf_counter_ns() - t0)
                return None
            out, placed = self._pack_and_decide(
                n0, cols, lane_item, owner_count, now_ms, t0, seams)
            if tq:
                self.profiler.lock_hold(
                    "submit_columnar", time.perf_counter_ns() - t0)
        return (out, placed, leftover, n0)

    def _raise_overcommit(self):
        raise RuntimeError(
            "key directory over-committed: "
            f">{self.plan.capacity_per_shard} distinct keys on one shard "
            "in one lookup")

    # ---- one window's stamps, at Engine's seams and under its names
    # (obs/profile.py PHASES). Every native entry goes _stamp_lock_wait ->
    # _stamp_lock_held -> _pack_and_decide on the launching side and
    # _collect -> _book_collect on the collecting side; each clock read
    # feeds the profiler's phase and the stats["*_ns"] timer that share it.

    def _stamp_lock_wait(self):
        """(seams, tq) of a window about to take the engine lock: the
        capture's span chain, opened on `lock_wait`, and the clock the
        wait runs from (0 with the profiler off)."""
        prof = self.profiler
        seams = prof.seams()  # host spans, while a capture runs
        tq = time.perf_counter_ns() if prof.enabled else 0
        seams("lock_wait")
        return seams, tq

    def _stamp_lock_held(self, site: str, tq: int, seams) -> int:
        """First thing under the engine lock: books the wait under `site`,
        opens the `prep` span and returns the clock prep runs from (it
        excludes the lock wait)."""
        t0 = time.perf_counter_ns()
        if tq:
            self.profiler.lock_wait(site, t0 - tq)
        seams("prep")
        return t0

    def _vacant_buffer(self, *shape: int) -> np.ndarray:
        """An i64 staging buffer [..., 9, w] with every lane vacant (slot
        row -1); an `alloc` span in a capture, inside its caller's
        `prep`."""
        with self.profiler.span("alloc"):
            packed = np.zeros(shape, np.int64)
            packed[..., 0, :] = -1
        return packed

    def _pack_and_decide(self, n0, cols, lane_item, owner_count, now_ms,
                         t0, seams):
        """Pack owner-major staging cols into the [R,S,9,w] mesh buffer
        and dispatch one shard_map'ped window — the ONE copy of the mesh
        packing contract, shared by the object and columnar fast paths.
        Returns (_dispatch_mesh handle, placed) with placed rows
        (r, s, None, lanes), or (None, []) when no lane was routed;
        readback via _collect. Caller holds the lock and has just routed
        the window's `n0` lanes: `t0` is _stamp_lock_held's clock and
        `seams` its span chain, closed here. Counted and stamped here:
        requests, batches, rounds, lanes_max; prep_ns (the route), pack_ns
        and the `prep` phase over both; device_ns and `dispatch`."""
        t1 = time.perf_counter_ns()
        prof = self.profiler
        self.stats["prep_ns"] += t1 - t0
        self.stats["requests"] += n0
        self.stats["batches"] += 1
        if not n0:
            prof.observe("prep", t1 - t0)
            seams(None)
            return None, []
        R, S = self.plan.n_regions, self.plan.n_shards
        counts = owner_count.tolist()
        fullest = max(counts)
        w = bucket_width(fullest, self.min_width, self.max_width)
        packed = self._vacant_buffer(R, S, 9, w)
        placed = []
        lanes = lane_item.tolist()
        pos = 0
        for o, cnt in enumerate(counts):
            if not cnt:
                continue
            r_, s_ = self.plan.owner_coords(o)
            packed[r_, s_, :, :cnt] = cols[:, pos:pos + cnt]
            placed.append((r_, s_, None, lanes[pos:pos + cnt]))
            pos += cnt
        t2 = time.perf_counter_ns()
        self.stats["pack_ns"] += t2 - t1
        self.stats["rounds"] += 1
        self.stats["lanes_max"] += fullest
        prof.observe("prep", t2 - t0)
        seams("dispatch")
        handle = self._dispatch_mesh(packed, now_ms)
        td = time.perf_counter_ns()
        seams(None)
        self.stats["device_ns"] += td - t2
        prof.observe("dispatch", td - t2)
        return handle, placed

    def _collect(self, out, placed, demux, *into):
        """Block on one dispatched mesh window (the device sync for THIS
        window) and hand its rows to `demux(rows, placed, *into)`,
        _scatter_columns or _demux, which returns its OVER_LIMIT count.
        Stamps `readback` and `demux`; needs no lock. Returns what
        _book_collect counts under it."""
        prof = self.profiler
        seams = prof.seams()
        seams("readback")
        t0 = time.perf_counter_ns()
        rows, nbytes = self._fetch_mesh(out)
        t1 = time.perf_counter_ns()
        seams("demux")
        over = demux(rows, placed, *into)
        t2 = time.perf_counter_ns()
        seams(None)
        prof.observe("readback", t1 - t0)
        prof.observe("demux", t2 - t1)
        return over, nbytes, t1 - t0, t2 - t1

    def _book_collect(self, over: int, fetched: int, readback_ns: int,
                      demux_ns: int) -> None:
        """Count one collected window. Caller holds the engine lock:
        completers run concurrently and the counters stay exact."""
        self.stats["over_limit"] += over
        self.stats["fetched_bytes"] += fetched
        self.stats["device_ns"] += readback_ns
        self.stats["demux_ns"] += demux_ns

    @staticmethod
    def _scatter_columns(rows, placed, o_st, o_li, o_re, o_rs) -> int:
        """The columnar demux: each owner block's response rows to their
        item positions in the caller's columns."""
        over_status = int(Status.OVER_LIMIT)
        over = 0
        for r_, s_, _k, lanes in placed:
            blk = rows[r_, s_]
            cnt = len(lanes)
            li = np.asarray(lanes, np.int64)
            o_st[li] = blk[0, :cnt]
            o_li[li] = blk[1, :cnt]
            o_re[li] = blk[2, :cnt]
            o_rs[li] = blk[3, :cnt]
            over += int(np.count_nonzero(blk[0, :cnt] == over_status))
        return over

    def complete_columnar(self, handle, out_status, out_limit,
                          out_remaining, out_reset) -> np.ndarray:
        """Read back a submitted mesh window and scatter the owner blocks'
        response rows to their item positions. Returns leftover indices
        (run them through the request-object path AFTER this round).
        Stamps readback and demux outside the lock, as
        Engine.complete_columnar."""
        out, placed, leftover, n0 = handle
        if n0:
            booked = self._collect(
                out, placed, self._scatter_columns, out_status, out_limit,
                out_remaining, out_reset)
            with self._lock:
                self._book_collect(*booked)
        return leftover

    # ------------------------------------------- pipelined columnar serving
    # Mesh twin of Engine.launch_columnar_windows (models/engine.py has
    # the full ordering argument): one shard_map launch per window, no
    # readback between launches, group cut on the first window that
    # yields leftovers. Shared stamps: per window lock_wait at site
    # `launch_columnar_windows`, prep and dispatch on the launch; readback
    # and demux on the collect.

    def launch_columnar_windows(self, windows, slow_mask: int,
                                now_ms: Optional[int] = None, staging=None):
        """Dispatch a PREFIX of 1..K columnar sub-windows over the mesh
        without blocking on any readback. Same wire layout and handle
        contract as Engine.launch_columnar_windows: handle[0] is the
        consumed-window meta list (each meta's last element the leftover
        indices), handle[1] an over-commit message or None. `staging` is
        accepted for contract parity (the mesh packer allocates per
        window)."""
        if not self.supports_columnar():
            return None
        if not windows or any(not 0 < wc[0] <= self.max_width
                              for wc in windows):
            return None
        if now_ms is None:
            now_ms = millisecond_now()
        metas = []
        failed = None
        for k, wc in enumerate(windows):
            (n, keys, key_off, name_len, hits, limit, duration,
             algorithm, behavior) = wc
            seams, tq = self._stamp_lock_wait()
            with self._lock:
                t0 = self._stamp_lock_held("launch_columnar_windows", tq,
                                           seams)
                n0, cols, lane_item, owner_count, leftover = \
                    native.prep_route_columnar(
                        self.directories, n, keys, key_off, name_len,
                        hits, limit, duration, algorithm, behavior,
                        slow_mask | _SLOW_MASK)
                if n0 < 0:
                    seams(None)
                if n0 == PREP_OVERCOMMIT:
                    # earlier windows already dispatched; this one and the
                    # rest are not consumed (caller error-fills them)
                    failed = ("key directory over-committed: "
                              f">{self.plan.capacity_per_shard} distinct "
                              "keys on one shard in one lookup")
                    break
                if n0 < 0:
                    if k == 0:
                        return None  # nothing mutated: object fallback
                    # defensive: nothing committed for THIS window — it
                    # retires whole through the caller's leftover path
                    metas.append((0, None, [],
                                  np.arange(n, dtype=np.int32)))
                    break
                out, placed = self._pack_and_decide(
                    n0, cols, lane_item, owner_count, now_ms, t0, seams)
                metas.append((n0, out, placed, leftover))
                if tq:
                    self.profiler.lock_hold(
                        "launch_columnar_windows", time.perf_counter_ns() - t0)
            if len(leftover):
                break  # group-cut barrier: leftovers retire first
        return (metas, failed)

    def collect_columnar_windows(self, handle, outs):
        """Block on a launched columnar group's mesh readbacks (in launch
        order) and scatter each window's owner blocks into the caller's
        column buffers. Same contract as Engine.collect_columnar_windows."""
        metas, _failed = handle
        leftovers = []
        for (n0, out, placed, leftover), cols in zip(metas, outs):
            if n0:
                booked = self._collect(out, placed, self._scatter_columns,
                                       *cols)
                with self._lock:
                    self._book_collect(*booked)
            leftovers.append(leftover)
        return leftovers

    # ----------------------------------------------------- pipelined serving
    # Launch/collect split for the combiner's depth-N pipeline
    # (models/engine.py has the single-chip twin, Engine.launch_windows /
    # collect_windows, and the ordering argument). Mesh groups launch one
    # shard_map window per member — still zero readbacks between
    # launches, so depth cycles overlap. Shared stamps: per window
    # lock_wait at site `launch_windows`, prep and dispatch on the launch;
    # readback and demux on the collect.

    def supports_pipeline(self) -> bool:
        """True when the non-blocking launch/collect split is available
        (native routing prep, no Store hooks)."""
        return self._prep_fast is not None and self.store is None

    def launch_windows(self, windows, now_ms: Optional[int] = None,
                       staging=None):
        """Dispatch 1..K request-object windows without blocking on any
        readback (one mesh launch per window, state-chained). Returns an
        opaque handle for collect_windows, or None when the pipelined
        path cannot take the group at all (nothing mutated)."""
        if not self.supports_pipeline():
            return None
        if not windows or any(not 0 < len(wk) <= self.max_width
                              for wk in windows):
            return None
        if now_ms is None:
            now_ms = millisecond_now()
        meta = []
        tails = []
        for wk in windows:
            seams, tq = self._stamp_lock_wait()
            with self._lock:
                t0 = self._stamp_lock_held("launch_windows", tq, seams)
                n0, cols, lane_item, owner_count, leftover = self._prep_fast(
                    self.directories, wk, _SLOW_MASK)
                if n0 < 0:
                    seams(None)
                    if n0 == PREP_OVERCOMMIT:
                        self._raise_overcommit()
                    # defensive: nothing committed for THIS window — it
                    # retires whole through the python tail below
                    n0, out, placed = 0, None, []
                    leftover = np.arange(len(wk), dtype=np.int32)
                else:
                    out, placed = self._pack_and_decide(
                        n0, cols, lane_item, owner_count, now_ms, t0, seams)
                meta.append((n0, out, placed, leftover))
                if tq:
                    self.profiler.lock_hold(
                        "launch_windows", time.perf_counter_ns() - t0)
            # Leftover tails retire NOW — after this window's dispatch,
            # BEFORE the next window preps — so a key pending in the tail
            # is never overtaken by its next arrival (per-key submission
            # order; models/engine.py has the full argument). Blocks on
            # its own readback; rare path.
            if leftover is not None and len(leftover):
                idxs = leftover.tolist()
                tails.append(self._slow_window(
                    [wk[i] for i in idxs], now_ms, count_batch=False))
            else:
                tails.append(None)
        return (windows, meta, tails)

    def collect_windows(self, handle):
        """Block on a launched group's readbacks (in launch order) and
        demux: one response list per window. Runs outside the engine lock
        except for the counter updates."""
        windows, meta, tails = handle
        results = []
        for k, wk in enumerate(windows):
            n0, out, placed, leftover = meta[k]
            responses: List[Optional[RateLimitResp]] = [None] * len(wk)
            if n0:
                booked = self._collect(out, placed, self._demux, responses)
                with self._lock:
                    self._book_collect(*booked)
            tail = tails[k]
            if tail is not None:
                for i, resp in zip(leftover.tolist(), tail):
                    responses[i] = resp
            results.append(responses)
        return results

    def launch_noop(self, width: Optional[int] = None):
        """All-padding mesh window dispatch (mutates nothing) for the
        combiner's depth auto-probe."""
        R, S = self.plan.n_regions, self.plan.n_shards
        w = width or self.min_width
        packed = np.zeros((R, S, 9, w), np.int64)
        packed[:, :, 0, :] = -1
        with self._lock:
            return self._dispatch_mesh(packed, 0)

    def collect_noop(self, handle) -> None:
        """Block on a launch_noop readback (its bytes are not the link
        counters': no request rode it)."""
        self._fetch_mesh(handle)

    def _slow_window(self, requests, now_ms,
                     count_batch: bool = True) -> List[RateLimitResp]:
        """The python pipeline (full validation, gregorian, GLOBAL mirror,
        duplicate rounds). `count_batch` is False for a fast window's
        leftover tail — the client batch was already counted there."""
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
            self.stats["prep_ns"] += prep_ns
            self.stats["requests"] += len(requests)
            self.stats["batches"] += 1 if count_batch else 0
            self.stats["errors"] += n_errors
            windows: List[List[WorkItem]] = []
            for round_work in rounds:
                kernel_items = []
                for item in round_work:
                    if self._try_answer_global(item, responses, now_ms):
                        continue
                    kernel_items.append(item)
                if kernel_items:
                    self.stats["rounds"] += 1
                    for start in range(0, len(kernel_items), self.max_width):
                        windows.append(
                            kernel_items[start : start + self.max_width])
            head, tail = self._split_scannable(windows)
            for wk in head:
                self._apply_round(wk, now_ms, responses)
            if tail:
                self._apply_rounds_scanned(tail, now_ms, responses)
            if tq:
                self.profiler.lock_hold(
                    "slow_window", time.perf_counter_ns() - t0)
        return responses  # type: ignore[return-value]

    def global_sync(self, now_ms: Optional[int] = None) -> int:
        """Run one psum sync window (reference: global.go runAsyncHits +
        runBroadcasts, collapsed). Returns the number of keys broadcast."""
        if now_ms is None:
            now_ms = millisecond_now()
        with self._lock:
            live = [(k, e) for k, e in self._globals.items()
                    if e.req is not None]
            if not live:
                return 0
            cfg = self._build_global_config(now_ms)
            delta = self._place_delta()
            # which keys actually carried hits this window, before zeroing:
            # the Store write-through below skips unchanged keys (the
            # reference fires OnChange only per applied hit, global.go:145)
            touched = {int(g) for g in np.nonzero(self._gdelta)[0]}
            self.state, mirror, _ = self._sync(self.state, delta, cfg, now_ms)
            # np.array (not asarray): the host mirror must be writable for
            # optimistic deduction between syncs
            self._mirror = GlobalMirror(*(np.array(c) for c in mirror))
            self._gdelta[:] = 0
            for _k, e in live:
                e.seen = True
            self.stats["global_syncs"] += 1
            if self.store is not None and touched:
                self._store_write_global(
                    [(k, e) for k, e in live if e.gidx in touched], cfg)
            self._sweep_globals(now_ms)
            return len(live)

    def global_pending_hits(self) -> int:
        return int(self._gdelta.sum())

    # ------------------------------------------------------------- internals

    def _try_answer_global(self, item: WorkItem, responses,
                           now_ms: int) -> bool:
        """Answer a GLOBAL request from the replicated mirror; queue its hits
        for the next sync. Returns False if the item must go to the kernel
        (not GLOBAL, or first touch)."""
        i, r, _ge, _gi = item
        if not has_behavior(r.behavior, Behavior.GLOBAL):
            return False
        key = r.hash_key()
        entry = self._globals.get(key)
        if entry is None:
            gidx = self._alloc_gidx(now_ms)
            if gidx < 0:
                # every slot has unsynced hits: serve this one
                # authoritatively and try again next touch
                self.stats["global_registry_fallbacks"] += 1
                return False
            entry = _GlobalEntry(gidx, self.owner_of(key), now_ms)
            self._globals[key] = entry
        else:
            self._globals.move_to_end(key)
        entry.req = r
        entry.last_ms = now_ms
        if not entry.seen:
            return False  # first touch: authoritative kernel path
        self._gdelta[entry.gidx] += r.hits
        self.stats["global_hits_queued"] += int(r.hits)
        self.stats["global_mirror_answers"] += 1
        # Optimistic local admission against the last broadcast: deduct hits
        # we can satisfy, reject the rest without deducting (token-bucket
        # response semantics, algorithms.go:107-133). Stricter than the
        # reference's frozen cached answer; authoritative state arrives with
        # the next broadcast.
        g = entry.gidx
        rem = int(self._mirror.remaining[g])
        st = int(self._mirror.status[g])
        if r.hits > 0:
            if rem == 0 or r.hits > rem:
                st = int(Status.OVER_LIMIT)
            else:
                rem -= r.hits
                self._mirror.remaining[g] = rem
        if st == Status.OVER_LIMIT:
            self.stats["over_limit"] += 1
        responses[i] = RateLimitResp(
            status=st,
            limit=int(self._mirror.limit[g]),
            remaining=rem,
            reset_time=int(self._mirror.reset_time[g]),
        )
        return True

    def _alloc_gidx(self, now_ms: int) -> int:
        """Claim a registry slot: free list, then high-water growth, then LRU
        eviction of a zero-delta entry. -1 when every slot holds unsynced
        hits (caller falls back to the authoritative path for one window)."""
        if self._gfree:
            return self._gfree.pop()
        if self._gnext < self.global_capacity:
            g = self._gnext
            self._gnext += 1
            return g
        # oldest-first iteration order: the first zero-delta entry IS the
        # LRU victim (entries with queued hits are skipped — evicting them
        # would lose hits); O(1) except when the oldest entries all hold
        # unsynced deltas
        for key, e in self._globals.items():
            if self._gdelta[e.gidx]:
                continue
            self._evict_global(key, e)
            return self._gfree.pop()
        return -1

    def _evict_global(self, key: str, entry: _GlobalEntry) -> None:
        """Drop one registered global key and recycle its gidx. The bucket
        row itself stays in the sharded table (its own expiry handles it);
        a re-registered key restarts on the first-touch authoritative path,
        exactly like a key evicted from the reference's LRU
        (cache.go:140-165)."""
        del self._globals[key]
        g = entry.gidx
        self._gdelta[g] = 0  # zero by precondition; keep it invariant
        self._mirror.status[g] = 0
        self._mirror.limit[g] = 0
        self._mirror.remaining[g] = 0
        self._mirror.reset_time[g] = 0
        self._gfree.append(g)
        self.stats["global_evictions"] += 1

    def _sweep_globals(self, now_ms: int) -> None:
        """Evict idle registered keys (no touch for global_idle_ms). Runs
        after a sync window, when every delta has just been flushed, so the
        zero-delta precondition holds for all live entries."""
        idle = [
            (k, e) for k, e in self._globals.items()
            if now_ms - e.last_ms > self.global_idle_ms
            and not self._gdelta[e.gidx]
        ]
        for k, e in idle:
            self._evict_global(k, e)

    def global_registry_size(self) -> int:
        return len(self._globals)

    def key_count(self) -> int:
        """Live key occupancy across every shard directory (the
        cache_size / engine_key_table_size gauge source)."""
        return sum(len(d) for d in self.directories)

    # Same fast-path bounds as models/engine.py: scan groups are capped at 32
    # windows of exactly min_width lanes, so warmup() can pre-compile every
    # shape this path dispatches, and the capacity guard keeps a group's
    # up-front directory lookups from recycling a slot an earlier window in
    # the group already claimed.
    _MAX_SCAN = 32

    def _split_scannable(self, windows: List[List[WorkItem]]):
        """Per-round head + scannable tail; see Engine._split_scannable.

        Round sizes only shrink, so the small duplicate-key rounds the scan
        path exists for always trail the list; wide windows keep the
        per-round path (already one amortized dispatch). A Store keeps the
        scan path (models/engine.py r3 parity): ONE read-through before
        the tail over the union of its keys, ONE write-through after with
        each key's FINAL row — resolved slot/fresh maps thread through
        _pack_lanes so no re-lookup strips a fresh flag (PARITY #8)."""
        if len(windows) <= 1:
            return windows, []
        split = len(windows)
        while split > 0 and len(windows[split - 1]) <= self.min_width:
            split -= 1
        tail = windows[split:]
        if (len(tail) < 2 or
                sum(len(w) for w in tail) * 4 > self.plan.capacity_per_shard):
            return windows, []
        return windows[:split], tail

    def _route_lanes(self, round_work: List[WorkItem]):
        """Split a window's items by owner chip (host-side lane routing)."""
        lanes: List[List[WorkItem]] = [[] for _ in range(self.plan.n_owners)]
        for item in round_work:
            lanes[self.owner_of(item[1].hash_key())].append(item)
        return lanes

    def _pack_lanes(self, lanes, w: int, packed, placed, k: Optional[int],
                    pre=None):
        """Fill one window's [R,S,9,w] slice (packed[..., k, :, :] when k is
        given) and record one (r, s, k, [resp indices]) demux group per
        owner lane-run (lanes 0..n-1 in index order — _demux's contract).

        `pre`, when given, maps owner -> (slots, fresh) already resolved by
        the caller (the Store path looks keys up before read-through)."""
        self.stats["lanes_max"] += max(map(len, lanes))
        for owner, items in enumerate(lanes):
            if not items:
                continue
            r_, s_ = self.plan.owner_coords(owner)
            t = time.perf_counter_ns()
            if pre is None:
                keys = [it[1].hash_key() for it in items]
                slots, fresh = self.directories[owner].lookup(keys)
            else:
                slots, fresh = pre[owner]
            t2 = time.perf_counter_ns()
            self.stats["lookup_ns"] += t2 - t
            dst = packed[r_, s_] if k is None else packed[r_, s_, k]
            pack_window(items, slots, fresh, w, out=dst)
            self.stats["pack_ns"] += time.perf_counter_ns() - t2
            # one demux group per owner lane-run: lanes are 0..n-1 in item
            # order, so the group carries just the response indices
            placed.append((r_, s_, k, [item[0] for item in items]))

    @staticmethod
    def _demux(out, placed, responses) -> int:
        """Demux one readback buffer into responses.

        `placed` rows are (r, s, k, [resp indices]) — one group per owner
        lane-run, lanes 0..n-1 in index order; k is None outside the scan
        path. Response row order is decide_packed's output contract. One
        C-level tolist per group beats four per-element int() casts.
        Returns the OVER_LIMIT answers, which the caller counts under the
        engine lock."""
        over_status = int(Status.OVER_LIMIT)
        over = 0
        for r_, s_, k, idxs in placed:
            row = out[r_, s_] if k is None else out[r_, s_, k]
            status, limit, remaining, reset = row[:, :len(idxs)].tolist()
            over += status.count(over_status)
            for j, i in enumerate(idxs):
                responses[i] = RateLimitResp(
                    status=status[j], limit=limit[j],
                    remaining=remaining[j], reset_time=reset[j])
        return over

    @staticmethod
    def _row_snapshot(rows, r_: int, s_: int, j: int, key: str):
        """One gathered-rows lane ([R,S,7,W] buffer, make_gather_sharded's
        row order = TableState field order) as a host BucketSnapshot."""
        from gubernator_tpu.store import BucketSnapshot

        return BucketSnapshot(
            key=key, algo=int(rows[r_, s_, 0, j]),
            limit=int(rows[r_, s_, 1, j]),
            remaining=int(rows[r_, s_, 2, j]),
            duration=int(rows[r_, s_, 3, j]),
            stamp=int(rows[r_, s_, 4, j]),
            expire_at=int(rows[r_, s_, 5, j]),
            status=int(rows[r_, s_, 6, j]))

    def _apply_rounds_scanned(self, windows, now_ms, responses) -> None:
        """Retire every scannable window in ⌈N/32⌉ mesh dispatches.

        The per-round path pays one full shard_map dispatch per duplicate-key
        round; a hot-key herd of d duplicates costs d launches. Here each
        chip scans up to 32 windows of its own lanes in one launch."""
        R, S = self.plan.n_regions, self.plan.n_shards
        w = self.min_width  # _split_scannable guarantees every window fits

        # Store hooks batch around the WHOLE tail (models/engine.py r3
        # parity): one read-through over the union of its keys, one
        # write-through after with final rows. Per-window slot/fresh come
        # from the union lookup's maps — a re-lookup would strip the fresh
        # flag of a first-occurrence key in a later tail window. `fresh`
        # is consumed by the key's first window.
        store_ctx = None
        slot_map = fresh_map = None
        if self.store is not None and windows:
            seen_items = {}
            for wk in windows:
                for item in wk:
                    seen_items.setdefault(item[1].hash_key(), item)
            union_items = list(seen_items.values())
            _lanes, per_owner, slotmat, _wu = \
                self._store_lookup_owners(union_items, unbounded=True)
            self._store_read_through_mesh(per_owner, slotmat, now_ms)
            slot_map, fresh_map = {}, {}
            for _o, _r, _s, _items, keys, slots, fresh in per_owner:
                for j, key in enumerate(keys):
                    slot_map[key] = slots[j]
                    if fresh[j]:
                        fresh_map[key] = True
            store_ctx = (per_owner, slotmat)

        def window_pre(lanes):
            if store_ctx is None:
                return None
            pre = {}
            for owner, items in enumerate(lanes):
                if not items:
                    continue
                ks = [it[1].hash_key() for it in items]
                pre[owner] = ([slot_map[k] for k in ks],
                              [fresh_map.pop(k, False) for k in ks])
            return pre

        for g0 in range(0, len(windows), self._MAX_SCAN):
            group = windows[g0:g0 + self._MAX_SCAN]
            if len(group) == 1:
                # trailing singleton rides the warmed single-window
                # program; inside a store tail it reuses the union's
                # resolved maps (its keys are covered by the batched hooks)
                lanes = self._route_lanes(group[0])
                self._apply_round(group[0], now_ms, responses,
                                  pre=window_pre(lanes), lanes=lanes)
                continue
            seams = self.profiler.seams()
            seams("prep")
            t_prep = time.perf_counter_ns()
            k_pad = _bucket_pow2(len(group))
            # vacant lanes, the pad windows' among them
            packed = self._vacant_buffer(R, S, k_pad, 9, w)
            placed: List[Tuple[int, int, Optional[int], List[int]]] = []
            for k, wk in enumerate(group):
                lanes = self._route_lanes(wk)
                self._pack_lanes(lanes, w, packed, placed, k,
                                 pre=window_pre(lanes))

            self._decide_and_demux(self._dispatch_mesh_scan, packed, now_ms,
                                   placed, responses, t_prep, seams)

        if store_ctx is not None:
            per_owner, slotmat = store_ctx
            self._store_write_through_mesh(per_owner, slotmat, now_ms)

    # -------------------------------------------------- staging dispatch
    # Every mesh window funnels through these helpers so the wide/lean
    # wire-format switch lives in one place (models/engine.py has the
    # single-chip twin, Engine._dispatch_staged / _fetch_staged; both
    # stamp the sub-phases and count the link's bytes inside them, the
    # callers stamp the phases around them). The handle defers the
    # device sync: the columnar path reads it back in complete_columnar,
    # everyone else via _fetch_mesh immediately.

    def _dispatch_mesh(self, packed: np.ndarray, now_ms):
        """One wide i64[R,S,9,w] window, shipped on the 4 B/lane lean
        wire when eligible. Returns an opaque handle for _fetch_mesh."""
        prof = self.profiler
        t_in = time.perf_counter_ns() if prof.enabled else 0
        sub = prof.seams()  # nested in the caller's `dispatch`
        sub("stage")
        if self._staging != "wide" and self._lean_ok:
            ln = lean_window(packed, self.plan.capacity_per_shard)
            if ln is not None:
                return self._launch_mesh(self._decide_lean, ln, packed,
                                         now_ms, t_in, sub), now_ms
        return self._launch_mesh(self._decide, None, packed, now_ms, t_in,
                                 sub), None

    def _dispatch_mesh_scan(self, stacked: np.ndarray, now_ms):
        """decide_scan dispatch of a wide i64[R,S,K,9,w] stack, shipped
        lean when eligible. Handle contract matches _dispatch_mesh."""
        prof = self.profiler
        t_in = time.perf_counter_ns() if prof.enabled else 0
        sub = prof.seams()
        sub("stage")
        if self._staging != "wide" and self._lean_ok:
            ln = lean_window(stacked, self.plan.capacity_per_shard)
            if ln is not None:
                return self._launch_mesh(self._decide_scan_lean, ln,
                                         stacked, now_ms, t_in, sub), now_ms
        return self._launch_mesh(self._decide_scan, None, stacked, now_ms,
                                 t_in, sub), None

    def _launch_mesh(self, fn, lean, wide, now_ms, t_in: int, sub):
        """The one mesh launch (Engine._launch's twin): `fn` over the wide
        buffer `wide`, or over its `lean` form (lanes, cfg) where
        lean_window gave one. Everything since the funnel's entry at
        `t_in` (the lean attempt; 0: the profiler is off) was `stage`; the
        placement onto the chips and the jitted call are `launch`. `sub`
        is the funnel's span chain, closed here. Caller holds the engine
        lock."""
        st = self.stats
        if lean is None:
            st["staged_bytes"] += wide.nbytes
        else:
            st["lean_windows"] += 1
            st["staged_bytes"] += lean[0].nbytes + lean[1].nbytes
        sub("launch")
        t = time.perf_counter_ns() if t_in else 0
        if lean is None:
            self.state, out = fn(self.state, wide, now_ms)
        else:
            self.state, out = fn(self.state, jnp.asarray(lean[0]),
                                 jnp.asarray(lean[1]), now_ms)
        sub(None)
        if t_in:
            t2 = time.perf_counter_ns()
            prof = self.profiler
            prof.observe_sub("stage", t - t_in)
            prof.observe_sub("launch", t2 - t)
        return out

    def _fetch_mesh(self, handle):
        """Block on a dispatched mesh window and return (the wide i64
        response rows regardless of which wire format carried it, the
        bytes copied back for them). Needs no lock. While a capture runs
        the wait for the chips is made apart from the copy (`device_wait`,
        then the copy and its widening as `fetch`: both inside the
        caller's `readback`), and only then: Engine._fetch_staged says
        what that costs."""
        out, lean_now = handle
        prof = self.profiler
        split = prof.capturing
        if split:
            sub = prof.seams()  # nested in the caller's `readback`
            sub("device_wait")
            t0 = time.perf_counter_ns()
            out.block_until_ready()
            t1 = time.perf_counter_ns()
            sub("fetch")
        rows = np.asarray(out)
        if lean_now is not None:
            rows = widen_compact_out(rows, lean_now)
        if split:
            t2 = time.perf_counter_ns()
            sub(None)
            prof.observe_sub("device_wait", t1 - t0)
            prof.observe_sub("fetch", t2 - t1)
        return rows, out.nbytes

    def _decide_and_demux(self, dispatch, packed, now_ms, placed, responses,
                          t_prep: int, seams) -> None:
        """The python pipeline's launch of one packed buffer through
        `dispatch` (_dispatch_mesh or _dispatch_mesh_scan), its readback
        and its demux into `responses`, stamped as Engine._apply_round
        stamps its own: routing, lookup and pack since `t_prep` are `prep`,
        the span `seams` has open; the chain is closed here. Caller holds
        the engine lock."""
        prof = self.profiler
        seams("dispatch")
        t = time.perf_counter_ns()
        handle = dispatch(packed, now_ms)
        td = time.perf_counter_ns()
        seams("readback")
        out, nbytes = self._fetch_mesh(handle)
        t2 = time.perf_counter_ns()
        seams("demux")
        self.stats["over_limit"] += self._demux(out, placed, responses)
        t3 = time.perf_counter_ns()
        seams(None)
        self.stats["fetched_bytes"] += nbytes
        self.stats["device_ns"] += t2 - t
        self.stats["demux_ns"] += t3 - t2
        prof.observe("prep", t - t_prep)
        prof.observe("dispatch", td - t)
        prof.observe("readback", t2 - td)
        prof.observe("demux", t3 - t2)

    def _apply_round(self, round_work: List[WorkItem], now_ms, responses,
                     pre=None, lanes=None) -> None:
        """One window, one mesh dispatch. `pre` (owner -> (slots, fresh))
        marks a tail singleton inside _apply_rounds_scanned's store tail,
        whose batched read/write-through already covers these keys
        (`lanes` carries the caller's routing so it isn't redone)."""
        if self.store is not None and pre is None:
            return self._apply_round_store(round_work, now_ms, responses)
        R, S = self.plan.n_regions, self.plan.n_shards
        seams = self.profiler.seams()
        seams("prep")
        t_prep = time.perf_counter_ns()
        if lanes is None:
            lanes = self._route_lanes(round_work)
        w = bucket_width(
            max(len(l) for l in lanes), self.min_width, self.max_width)

        # one i64[R,S,9,w] staging buffer up, one i64[R,S,4,w] back
        # (row order must match make_decide_sharded's unpack)
        packed = self._vacant_buffer(R, S, 9, w)
        placed: List[Tuple[int, int, Optional[int], List[int]]] = []
        self._pack_lanes(lanes, w, packed, placed, None, pre=pre)
        self._decide_and_demux(self._dispatch_mesh, packed, now_ms, placed,
                               responses, t_prep, seams)

    def _store_lookup_owners(self, work_items: List[WorkItem],
                             unbounded: bool = False):
        """Route + per-owner directory lookup for the Store paths.
        Returns (lanes, per_owner rows (owner, r, s, items, keys, slots,
        fresh), slotmat [R,S,w], w). `unbounded` lifts the max_width clamp:
        the scan tail's UNION spans many windows, and its slotmat only
        feeds the store gather/inject — never a decide window — so its
        lane width must fit the union, not the kernel."""
        R, S = self.plan.n_regions, self.plan.n_shards
        lanes = self._route_lanes(work_items)
        mx = max(len(l) for l in lanes)
        cap = max(self.max_width, _bucket_pow2(mx)) if unbounded \
            else self.max_width
        w = bucket_width(mx, self.min_width, cap)
        per_owner = []  # (owner, r, s, items, keys, slots, fresh)
        slotmat = np.full((R, S, w), -1, np.int32)
        t = time.perf_counter_ns()
        for owner, items in enumerate(lanes):
            if not items:
                continue
            r_, s_ = self.plan.owner_coords(owner)
            keys = [it[1].hash_key() for it in items]
            slots, fresh = self.directories[owner].lookup(keys)
            slotmat[r_, s_, :len(slots)] = slots
            per_owner.append((owner, r_, s_, items, keys, slots, list(fresh)))
        self.stats["lookup_ns"] += time.perf_counter_ns() - t
        return lanes, per_owner, slotmat, w

    def _store_read_through_mesh(self, per_owner, slotmat, now_ms) -> None:
        """Consult the store for rows the table can't serve (reference:
        algorithms.go:26-33); injects returned rows and flips their fresh
        flags (per_owner's fresh lists mutate in place)."""
        R, S = self.plan.n_regions, self.plan.n_shards
        w = slotmat.shape[-1]
        t = time.perf_counter_ns()
        rows = np.asarray(self._gather(self.state, slotmat))  # [R,S,7,w]
        inj_slot = np.full((R, S, w), -1, np.int32)
        inj_rows = np.zeros((R, S, 7, w), np.int64)
        inj_n = [0] * self.plan.n_owners
        for owner, r_, s_, items, keys, slots, fresh in per_owner:
            for j, (_i, r, _ge, _gi) in enumerate(items):
                algo = int(rows[r_, s_, 0, j])
                live = (not fresh[j] and algo >= 0
                        and now_ms <= int(rows[r_, s_, 5, j]))
                if live and algo != int(r.algorithm):
                    # algorithm switch discards the old bucket everywhere
                    # (reference: algorithms.go:54-62)
                    self.store.remove(keys[j])
                    live = False
                if live:
                    continue
                item = self.store.get(r)
                if item is None:
                    continue
                k = inj_n[owner]
                inj_n[owner] = k + 1
                inj_slot[r_, s_, k] = slots[j]
                inj_rows[r_, s_, :, k] = (
                    item.algo, item.limit, item.remaining, item.duration,
                    item.stamp, item.expire_at, item.status)
                fresh[j] = False  # the injected row is now live
        if any(inj_n):
            self.state = self._inject(self.state, inj_slot, inj_rows)
        self.stats["store_ns"] += time.perf_counter_ns() - t

    def _store_write_through_mesh(self, per_owner, slotmat, now_ms) -> None:
        """Report post-decision rows (reference: algorithms.go:64-68,
        175-177); discarded buckets get remove + directory drop."""
        t = time.perf_counter_ns()
        rows = np.asarray(self._gather(self.state, slotmat))
        for owner, r_, s_, items, keys, slots, fresh in per_owner:
            for j, (_i, r, _ge, _gi) in enumerate(items):
                if int(rows[r_, s_, 0, j]) < 0:
                    # token RESET_REMAINING cleared the row
                    # (reference: algorithms.go:37-39)
                    self.store.remove(keys[j])
                    self.directories[owner].drop(keys[j])
                    continue
                self.store.on_change(
                    r, self._row_snapshot(rows, r_, s_, j, keys[j]))
        self.stats["store_ns"] += time.perf_counter_ns() - t

    def _apply_round_store(self, round_work: List[WorkItem], now_ms,
                           responses) -> None:
        """Store-aware round: read-through before the kernel, write-through
        after, per owner lane. Mirrors models/engine.py
        _store_read_through/_store_write_through (reference:
        algorithms.go:26-33,64-68,175-177); the extra cost is two mesh row
        gathers and at most one row inject per window — all staged through
        single [R,S,...] buffers like the decide path itself."""
        R, S = self.plan.n_regions, self.plan.n_shards
        lanes, per_owner, slotmat, w = self._store_lookup_owners(round_work)
        self._store_read_through_mesh(per_owner, slotmat, now_ms)

        # ---- decide ------------------------------------------------------
        seams = self.profiler.seams()
        seams("prep")
        t_prep = time.perf_counter_ns()  # the store's own time is store_ns
        packed = self._vacant_buffer(R, S, 9, w)
        placed: List[Tuple[int, int, Optional[int], List[int]]] = []
        pre = {owner: (slots, fresh)
               for owner, _r, _s, _items, _keys, slots, fresh in per_owner}
        self._pack_lanes(lanes, w, packed, placed, None, pre=pre)
        self._decide_and_demux(self._dispatch_mesh, packed, now_ms, placed,
                               responses, t_prep, seams)

        self._store_write_through_mesh(per_owner, slotmat, now_ms)

    def _build_global_config(self, now_ms: int) -> GlobalConfig:
        import datetime as _dt

        from gubernator_tpu.utils.gregorian import (
            gregorian_duration,
            gregorian_expiration,
        )

        G = self.global_capacity
        slot = np.full((G,), -1, np.int32)
        owner = np.zeros((G,), np.int32)
        limit = np.zeros((G,), np.int64)
        duration = np.zeros((G,), np.int64)
        algorithm = np.zeros((G,), np.int32)
        behavior = np.zeros((G,), np.int32)
        greg_expire = np.zeros((G,), np.int64)
        greg_interval = np.zeros((G,), np.int64)
        fresh = np.zeros((G,), np.bool_)
        by_owner: Dict[int, List[Tuple[str, _GlobalEntry]]] = {}
        for key, e in self._globals.items():
            if e.req is not None:
                by_owner.setdefault(e.owner, []).append((key, e))
        local_now = _dt.datetime.fromtimestamp(now_ms / 1000.0)
        for own, entries in by_owner.items():
            slots, fr = self.directories[own].lookup([k for k, _ in entries])
            for (key, e), s_, f_ in zip(entries, slots, fr):
                g = e.gidx
                slot[g] = s_
                owner[g] = own
                limit[g] = e.req.limit
                duration[g] = e.req.duration
                algorithm[g] = int(e.req.algorithm)
                # the broadcast re-applies with the GLOBAL flag stripped
                # (reference: global.go:209-214)
                behavior[g] = int(e.req.behavior) & ~int(Behavior.GLOBAL)
                fresh[g] = f_
                if has_behavior(e.req.behavior, Behavior.DURATION_IS_GREGORIAN):
                    greg_expire[g] = gregorian_expiration(local_now, e.req.duration)
                    greg_interval[g] = gregorian_duration(local_now, e.req.duration)
        return GlobalConfig(
            slot=jnp.asarray(slot),
            owner=jnp.asarray(owner),
            limit=jnp.asarray(limit),
            duration=jnp.asarray(duration),
            algorithm=jnp.asarray(algorithm),
            behavior=jnp.asarray(behavior),
            greg_expire=jnp.asarray(greg_expire),
            greg_interval=jnp.asarray(greg_interval),
            fresh=jnp.asarray(fresh),
        )

    def _store_write_global(self, live, cfg: GlobalConfig) -> None:
        """Write-through the rows a GLOBAL sync just rewrote.

        In the reference every hit an owner applies goes through getRateLimit
        and so fires Store.OnChange (algorithms.go:64-68 via global.go:145);
        here the sync applies aggregated deltas on device, so the hooks fire
        once per synced key per window — same persisted state, fewer calls."""
        R, S = self.plan.n_regions, self.plan.n_shards
        slot_np = np.asarray(cfg.slot)
        owner_np = np.asarray(cfg.owner)
        lanes = [0] * self.plan.n_owners
        placed = []  # (key, req, r, s, lane)
        width = bucket_width(
            max(1, len(live)), self.min_width, self.global_capacity)
        slotmat = np.full((R, S, width), -1, np.int32)
        for key, e in live:
            g = e.gidx
            if slot_np[g] < 0:
                continue
            r_, s_ = self.plan.owner_coords(int(owner_np[g]))
            k = lanes[int(owner_np[g])]
            lanes[int(owner_np[g])] = k + 1
            slotmat[r_, s_, k] = slot_np[g]
            placed.append((key, e.req, r_, s_, k))
        if not placed:
            return
        t = time.perf_counter_ns()
        rows = np.asarray(self._gather(self.state, slotmat))
        for key, req, r_, s_, k in placed:
            if int(rows[r_, s_, 0, k]) < 0:
                continue
            self.store.on_change(req, self._row_snapshot(rows, r_, s_, k, key))
        self.stats["store_ns"] += time.perf_counter_ns() - t

    def _place_delta(self) -> jax.Array:
        """This host's deltas enter the mesh on device (0, 0); psum makes
        placement irrelevant. Multi-host processes each fill their local row."""
        R, S = self.plan.n_regions, self.plan.n_shards
        delta = np.zeros((R, S, self.global_capacity), np.int64)
        delta[0, 0, :] = self._gdelta
        return jnp.asarray(delta)

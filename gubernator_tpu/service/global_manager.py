"""Host-tier GLOBAL pipelines: async hit forwarding + owner broadcast.

This is the cross-host half of Behavior=GLOBAL (reference: global.go:28-239).
Within one host's device mesh the same flows are a single psum step
(parallel/global_sync.py); between hosts they ride the PeersV1 RPC surface:

- hit pipeline (non-owner side): requests answered from the local cache queue
  their hits here; hits aggregate per key and flush to each key's owner host
  at `global_batch_limit` (1000) keys or `global_sync_wait` (500 µs)
  (reference: global.go:73-156).
- broadcast pipeline (owner side): every applied GLOBAL request queues an
  update; on flush the owner re-reads each key's authoritative state
  (hits=0, GLOBAL flag stripped) and pushes it to every other peer
  (reference: global.go:159-239).
"""

from __future__ import annotations

import dataclasses

import logging
import threading
import time
from typing import Dict, Optional

from gubernator_tpu.obs import witness
from gubernator_tpu.obs.profile import background_of
from gubernator_tpu.service.config import BehaviorConfig
from gubernator_tpu.service.convert import resp_to_pb
from gubernator_tpu.service.pb import peers_pb2 as peers_pb
from gubernator_tpu.types import Behavior, RateLimitReq, without_behavior

log = logging.getLogger("gubernator_tpu.global")


class _Pipeline:
    """Aggregate-by-key queue flushed at a cap or `wait_s` after the first
    enqueue into an empty queue (the Interval semantics of the reference's
    batching loops, interval.go:26-69 / global.go:73-112)."""

    def __init__(self, name: str, wait_s: float, limit: int, flush_fn,
                 observe=None, recorder=None):
        self._name = name
        self._wait_s = wait_s
        self._limit = limit
        self._recorder = recorder  # flight recorder (obs/events.py) or None
        self._hw_flagged = False  # edge state for the high-water event
        if observe is not None:
            # time every flush into a histogram, the reference's defer'd
            # duration observation (global.go:155,238)
            inner = flush_fn

            def flush_fn(batch, _inner=inner, _observe=observe):
                start = time.perf_counter()
                try:
                    _inner(batch)
                finally:
                    _observe(time.perf_counter() - start)

        self._flush_fn = flush_fn
        self._pending: Dict[str, RateLimitReq] = {}
        self._deadline: Optional[float] = None
        self._lock = witness.make_lock("global.manager")
        self._wake = threading.Event()
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name=f"global-{name}", daemon=True
        )
        self._thread.start()

    def queue(self, req: RateLimitReq, aggregate_hits: bool) -> None:
        with self._lock:
            # coalesce per key: latest authoritative state wins (broadcast)
            # or hits aggregate (async hits) — either way a hot key holds
            # ONE pending entry, so Zipf-head traffic cannot flood the
            # pipeline. The deadline arms only on the empty->non-empty
            # transition: re-queues of an already-pending key must neither
            # push the flush out (each re-arm used to reset the timer, so a
            # hot key could postpone its own flush indefinitely) nor fire a
            # wakeup per request.
            was_empty = not self._pending
            if aggregate_hits:
                prev = self._pending.get(req.hash_key())
                if prev is not None:
                    # same aggregation the reference applies before
                    # forwarding (global.go:81-88)
                    req = dataclasses.replace(req, hits=req.hits + prev.hits)
            self._pending[req.hash_key()] = req
            n = len(self._pending)
            if was_empty:
                self._deadline = time.monotonic() + self._wait_s
        if was_empty or n >= self._limit:
            self._wake.set()
        if n >= self._limit and not self._hw_flagged:
            # edge-triggered: the queue filled to its flush cap before the
            # wait window elapsed — sustained means the flusher is behind
            self._hw_flagged = True
            if self._recorder is not None:
                self._recorder.emit("global.queue_high_water",
                                    pipeline=self._name, depth=n,
                                    limit=self._limit)

    def depth(self) -> int:
        """Keys currently queued and not yet flushed (scrape-time gauge)."""
        with self._lock:
            return len(self._pending)

    def _drain(self) -> Dict[str, RateLimitReq]:
        with self._lock:
            out, self._pending = self._pending, {}
            self._deadline = None
        self._hw_flagged = False  # re-arm the high-water edge
        return out

    def _run(self) -> None:
        while not self._closed:
            with self._lock:
                n = len(self._pending)
                deadline = self._deadline
            if n == 0:
                self._wake.wait(timeout=0.1)
                self._wake.clear()
                continue
            delay = (deadline or 0) - time.monotonic()
            if n < self._limit and delay > 0:
                self._wake.wait(timeout=delay)
                self._wake.clear()
                with self._lock:
                    not_ready = (
                        len(self._pending) < self._limit
                        and self._deadline is not None
                        and time.monotonic() < self._deadline
                    )
                if not_ready and not self._closed:
                    continue
            batch = self._drain()
            if batch:
                try:
                    self._flush_fn(batch)
                except Exception:  # noqa: BLE001 — pipeline must survive peers dying
                    log.exception("%s flush failed", self._name)

    def flush_now(self) -> None:
        """Synchronous flush for tests and shutdown."""
        batch = self._drain()
        if batch:
            self._flush_fn(batch)

    def close(self) -> None:
        self._closed = True
        self._wake.set()
        self._thread.join(timeout=1.0)
        self.flush_now()


class GlobalManager:
    """Owns both GLOBAL pipelines for one Instance."""

    def __init__(self, instance, behaviors: BehaviorConfig, metrics=None,
                 admission=None):
        self.instance = instance
        self.conf = behaviors
        self.metrics = metrics
        # admission controller (instance.py): under pressure, GLOBAL
        # broadcasts are the FIRST work class to shed — see queue_update
        self.admission = admission
        recorder = getattr(instance, "recorder", None)

        def timed(site, flush_fn):
            """The flush as a unit of background work (obs/profile.py)."""
            def flush(batch):
                with background_of(instance, site):
                    flush_fn(batch)
            return flush

        self._hits = _Pipeline(
            "hits", behaviors.global_sync_wait_s, behaviors.global_batch_limit,
            timed("global.send_hits", self._send_hits),
            observe=metrics.async_durations.observe if metrics else None,
            recorder=recorder,
        )
        self._broadcasts = _Pipeline(
            "broadcast", behaviors.global_sync_wait_s,
            behaviors.global_batch_limit,
            timed("global.broadcast", self._broadcast),
            observe=metrics.broadcast_durations.observe if metrics else None,
            recorder=recorder,
        )
        self.stats = {"hits_sent": 0, "broadcasts_sent": 0, "broadcast_errors": 0}

    def queue_hit(self, req: RateLimitReq) -> None:
        """Non-owner: forward these hits to the owner on the next window
        (reference: global.go:63-65)."""
        self._hits.queue(req, aggregate_hits=True)

    def queue_update(self, req: RateLimitReq) -> None:
        """Owner: broadcast this key's state on the next window
        (reference: global.go:67-69).

        Under admission brownout the broadcast is DROPPED instead of
        queued: each broadcast window re-reads authoritative state, so a
        dropped update is regenerated by the key's next applied GLOBAL
        hit — making it the cheapest backlog on the node to not grow
        while the serving path is the thing that needs the capacity."""
        if self.admission is not None and self.admission.enabled \
                and self.admission.shed_broadcast():
            return
        self._broadcasts.queue(req, aggregate_hits=False)

    def depths(self) -> tuple:
        """(hit queue depth, broadcast queue depth) — the backlog a scrape
        sees between flush windows (global_queue_depth{pipeline=...})."""
        return self._hits.depth(), self._broadcasts.depth()

    def flush(self) -> None:
        self._hits.flush_now()
        self._broadcasts.flush_now()

    def close(self) -> None:
        self._hits.close()
        self._broadcasts.close()

    # ------------------------------------------------------------ internals

    def _send_hits(self, batch: Dict[str, RateLimitReq]) -> None:
        """Group aggregated hits by owner peer and relay them
        (reference: global.go:116-156)."""
        by_peer = {}
        for key, req in batch.items():
            try:
                peer = self.instance.get_peer(key)
            except Exception as e:  # noqa: BLE001 — skip just this key,
                # keep the rest of the window (reference: global.go:127-131)
                log.error("while getting peer for hash key '%s': %s", key, e)
                continue
            by_peer.setdefault(id(peer), (peer, []))[1].append(req)
        for peer, reqs in by_peer.values():
            if peer.info.is_owner:
                # our own host owns these keys — apply directly
                self.instance.apply_owner_batch(reqs)
            else:
                try:
                    resps = peer.get_peer_rate_limits(reqs)
                except Exception:  # noqa: BLE001
                    log.exception(
                        "error sending global hits to '%s'", peer.info.address
                    )
                    continue
                lm = getattr(self.instance, "leases", None)
                if lm is not None and lm.enabled:
                    # leased hot keys drain through this pipeline, so the
                    # owner's responses double as the lease renewal
                    # channel: grants in their metadata install here with
                    # zero extra RPCs — and a broken drain path stops
                    # renewal with it (service/leases.py)
                    lm.install_from_responses(reqs, resps,
                                              peer.info.address)
            self.stats["hits_sent"] += len(reqs)

    def _broadcast(self, batch: Dict[str, RateLimitReq]) -> None:
        """Re-read authoritative state and push it to every peer
        (reference: global.go:194-239)."""
        updates = []
        for key, req in batch.items():
            peek = dataclasses.replace(
                without_behavior(req, Behavior.GLOBAL), hits=0)
            resp = self.instance.apply_owner_batch([peek])[0]
            if resp.error:
                continue
            updates.append(
                peers_pb.UpdatePeerGlobal(
                    key=key,
                    status=resp_to_pb(resp),
                    algorithm=int(req.algorithm),
                )
            )
        if not updates:
            return
        for peer in self.instance.local_peers():
            if peer.info.is_owner:  # ourselves
                continue
            try:
                peer.update_peer_globals(updates)
                self.stats["broadcasts_sent"] += 1
            except Exception:  # noqa: BLE001
                self.stats["broadcast_errors"] += 1
                log.exception(
                    "error sending global updates to '%s'", peer.info.address
                )

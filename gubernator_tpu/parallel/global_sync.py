"""GLOBAL-behavior synchronization as mesh collectives.

The reference implements Behavior=GLOBAL with two async gRPC pipelines
(reference: global.go:73-156 hit-forwarding to the owner, global.go:159-239
owner broadcast to every peer). On a TPU mesh both pipelines collapse into
ONE compiled step with two psums:

1. hit aggregation: every device contributes its locally-accumulated hit
   deltas for all registered global keys; `psum` over ("region", "shard")
   yields the cluster-total hits per key — this *is* the reference's
   `sendHits` group-by-owner fan-in (global.go:116-156), minus the RPCs.
2. owner apply: each key's owner lane (and only it) scatters the summed hits
   through the ordinary decision kernel into its authoritative table shard —
   the reference's `GetPeerRateLimits`-at-owner path (gubernator.go:267-284).
3. broadcast: the owner's fresh RateLimitResp columns are masked to zero on
   non-owners and `psum`med again, leaving every device holding the same
   authoritative mirror — the reference's `UpdatePeerGlobals` fan-out
   (global.go:219-236) as a single collective.

Hosts answer GLOBAL requests from the (host-copied) mirror between syncs,
exactly like the reference's non-owner local-cache answer
(gubernator.go:226-247).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from gubernator_tpu.ops.decide import I32, ReqBatch, TableState, decide
from gubernator_tpu.parallel.mesh import (
    MeshPlan, REGION_AXIS, SHARD_AXIS)


class GlobalMirror(NamedTuple):
    """Replicated authoritative status of every registered global key
    (the payload of the reference's UpdatePeerGlobals, proto/peers.proto:49-53)."""

    status: jax.Array  # i32[G]
    limit: jax.Array  # i64[G]
    remaining: jax.Array  # i64[G]
    reset_time: jax.Array  # i64[G]


class GlobalConfig(NamedTuple):
    """Replicated per-global-key request config, maintained by the host from
    the latest request seen (the reference stores the whole RateLimitReq in
    its broadcast queue, global.go:194-217)."""

    slot: jax.Array  # i32[G] owner-shard table slot; -1 unregistered
    owner: jax.Array  # i32[G] linear mesh index of the owning device
    limit: jax.Array  # i64[G]
    duration: jax.Array  # i64[G]
    algorithm: jax.Array  # i32[G]
    behavior: jax.Array  # i32[G] (GLOBAL bit already stripped by the host)
    greg_expire: jax.Array  # i64[G]
    greg_interval: jax.Array  # i64[G]
    fresh: jax.Array  # bool[G] owner slot newly assigned


def make_global_sync(plan: MeshPlan, donate: bool = False):
    """Compile the one-step GLOBAL sync over the plan's mesh.

    Returns fn(state, delta, cfg, now) -> (state, mirror, zeroed delta):
    - state: sharded TableState [R, S, C]
    - delta: i64[R, S, G] — each device's local hit deltas (sharded)
    - cfg: GlobalConfig of replicated [G] arrays
    """
    S = plan.n_shards
    state_spec = P(REGION_AXIS, SHARD_AXIS, None, None)
    delta_spec = P(REGION_AXIS, SHARD_AXIS, None)
    rep = P()

    def _step(
        state: TableState, delta: jax.Array, cfg: GlobalConfig, now: jax.Array
    ) -> Tuple[TableState, GlobalMirror, jax.Array]:
        local_state = state.reshape(state.shape[-2:])  # u32[C, 16]
        local_delta = delta.reshape(delta.shape[-1:])  # i64[G]

        total = jax.lax.psum(local_delta, (REGION_AXIS, SHARD_AXIS))
        my_id = (
            jax.lax.axis_index(REGION_AXIS) * S + jax.lax.axis_index(SHARD_AXIS)
        ).astype(I32)
        mine = (cfg.owner == my_id) & (cfg.slot >= 0)

        reqs = ReqBatch(
            slot=jnp.where(mine, cfg.slot, -1),
            hits=total,
            limit=cfg.limit,
            duration=cfg.duration,
            algorithm=cfg.algorithm,
            behavior=cfg.behavior,
            greg_expire=cfg.greg_expire,
            greg_interval=cfg.greg_interval,
            fresh=cfg.fresh,
        )
        new_local, resp = decide(local_state, reqs, now)

        # the broadcast IS an all-reduce of owner-masked columns (non-owners
        # contribute zeros)
        cols = (resp.status.astype(jnp.int64), resp.limit,
                resp.remaining, resp.reset_time)
        summed = [
            jax.lax.psum(jnp.where(mine, c, jnp.zeros_like(c)),
                         (REGION_AXIS, SHARD_AXIS))
            for c in cols
        ]
        mirror = GlobalMirror(
            status=summed[0].astype(I32),
            limit=summed[1],
            remaining=summed[2],
            reset_time=summed[3],
        )
        new_state = new_local.reshape((1, 1) + new_local.shape)
        return new_state, mirror, jnp.zeros_like(delta)

    mapped = jax.shard_map(
        _step,
        mesh=plan.mesh,
        in_specs=(state_spec, delta_spec, rep, rep),
        out_specs=(state_spec, rep, delta_spec),
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())

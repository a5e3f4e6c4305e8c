"""Runtime introspection: the /v1/debug/vars snapshot.

One JSON document answering "what is this daemon doing right now" — the
expvar-style counterpart to /metrics (which carries the same families as
time series). Everything here is a read of live objects; nothing is
sampled or buffered, so the snapshot is as fresh as the calling request.
"""

from __future__ import annotations

from typing import Optional

# Version of the snapshot's shape. Bump when a section is renamed or its
# meaning changes; ADDING a section is not normally a bump (the schema is
# subset-stable — consumers must tolerate new sections). Pinned by
# tests/test_debug_schema.py.
# v2: always-present "history" and "keyspace" sections (capacity &
# keyspace cartography plane) — bumped because both are promised on
# every Instance, not merely tolerated.
# v3: always-present "reshard" section (live-resharding handoff plane) —
# promised on every Instance; "enabled" inside it tracks GUBER_RESHARD.
# v4: always-present "profile" section (continuous profiling plane,
# obs/profile.py) — serving-cycle phase shares, lock-wait sites, and
# capture accounting are promised on every Instance; "enabled" inside
# it tracks GUBER_PROFILE.
# v5: always-present "ledger" section (decision ledger & conservation
# audit plane, obs/ledger.py) — per-authority admit totals, minted
# budget, and violation counts are promised on every Instance;
# "enabled" inside it tracks GUBER_LEDGER.
# v6: always-present "autopilot" section (bounded closed-loop control
# plane, service/autopilot.py) — per-controller engagement/dwell/freeze
# state, knob bands, and the move/clamp/freeze counters are promised on
# every Instance; "enabled" inside it tracks GUBER_AUTOPILOT.
DEBUG_VARS_SCHEMA_VERSION = 6


def _backend_vars(backend) -> dict:
    out: dict = {"type": type(backend).__name__}
    stats = getattr(backend, "stats", None)
    if stats is not None:
        out["stats"] = stats.as_dict() if hasattr(stats, "as_dict") \
            else dict(stats)
    for attr in ("capacity", "min_width", "max_width"):
        v = getattr(backend, attr, None)
        if isinstance(v, int):
            out[attr] = v
    # platform / device kind / count / table bytes per device / donation /
    # key directory, as the daemon's boot line prints them
    # (utils/platform.py device_facts); absent on stub backends
    device = getattr(backend, "device", None)
    if isinstance(device, dict):
        out["device"] = dict(device)
        # two live facts beside the boot-time ones: the allocator's
        # account of each device, and the compiles since Ready (the
        # daemon hangs a CompileWatch on its backend; nulls without one)
        from gubernator_tpu.utils.platform import device_memory

        try:
            out["device"]["memory"] = device_memory(backend)
        except Exception:  # noqa: BLE001 — introspection must not raise
            out["device"]["memory"] = None
        watch = getattr(backend, "compile_watch", None)
        out["device"]["compiles"] = watch.facts() if watch is not None \
            else {"count": None, "seconds": None}
    occ = key_table_size(backend)
    if occ is not None:
        out["key_table_size"] = occ
    directory = _directory_vars(backend)
    if directory is not None:
        out["directory"] = directory
    reg = getattr(backend, "global_registry_size", None)
    if callable(reg):
        out["global_registry_size"] = int(reg())
    return out


def _directory_vars(backend) -> Optional[dict]:
    """What the host directory counts of its own churn: LRU evictions
    (`eviction_count`) and, on a single-table engine's native directory,
    the keys it has given a slot (`inserts`: every fresh lane is one) and
    the tombstone rebuilds of its bucket array (how many, their
    nanoseconds in all, the longest). None where evictions are not
    host-countable."""
    evictions = eviction_count(backend)
    if evictions is None:
        return None
    out = {"evictions": evictions}
    churn = getattr(getattr(backend, "directory", None), "churn_stats", None)
    if callable(churn):
        out.update(churn())
    return out


def key_table_size(backend) -> Optional[int]:
    """Live key-table occupancy: distinct keys currently holding a table
    slot. None when the backend has no countable directory (the devdir
    engine keeps keys on-chip as fingerprints only)."""
    count = getattr(backend, "key_count", None)
    if callable(count):
        try:
            return int(count())
        except Exception:  # noqa: BLE001 — introspection must not raise
            return None
    return None


def table_capacity(backend) -> Optional[int]:
    """Total key-table slot capacity across the backend's device table(s).
    None when the backend exposes neither a capacity attribute nor a mesh
    plan (a stub or store-only backend)."""
    cap = getattr(backend, "capacity", None)
    if isinstance(cap, int):
        return cap
    plan = getattr(backend, "plan", None)
    if plan is not None:
        try:
            return int(plan.n_owners) * int(plan.capacity_per_shard)
        except Exception:  # noqa: BLE001 — introspection must not raise
            return None
    return None


def eviction_count(backend) -> Optional[int]:
    """Cumulative key-table LRU evictions (slots recycled from live keys).
    None when eviction is not host-countable: the devdir engine evicts
    on-chip via probe epochs and keeps no host directory."""
    if getattr(backend, "fps", None) is not None:
        return None  # on-chip directory: evictions happen device-side
    d = getattr(backend, "directory", None)
    if d is not None:
        ev = getattr(d, "evictions", None)
        if ev is not None:
            try:
                return int(ev)
            except Exception:  # noqa: BLE001
                return None
    dirs = getattr(backend, "directories", None)
    if dirs:
        try:
            return sum(int(d.evictions) for d in dirs)
        except Exception:  # noqa: BLE001
            return None
    return None


def debug_vars(instance) -> dict:
    """Snapshot one Instance's pipeline state. Sections appear only when
    the corresponding subsystem is wired, so the schema is
    subset-stable across backend/deployment shapes."""
    from gubernator_tpu.ops.decide import kernel_telemetry

    out: dict = {
        "schema_version": DEBUG_VARS_SCHEMA_VERSION,
        "advertise_address": instance.advertise_address,
        "engine": _backend_vars(instance.backend),
        "combiner": dict(instance.combiner.stats),
        "kernel": kernel_telemetry.snapshot(),
    }

    gm = getattr(instance, "global_manager", None)
    if gm is not None:
        hits_depth, bcast_depth = gm.depths()
        out["global"] = {
            **gm.stats,
            "hits_queue_depth": hits_depth,
            "broadcast_queue_depth": bcast_depth,
            "cache_items": len(instance._global_cache),  # noqa: SLF001
        }

    with instance._peer_lock:  # noqa: SLF001 — the read the ring exposes
        out["peers"] = {
            "local": [
                {"address": p.info.address, "datacenter": p.info.datacenter,
                 "is_owner": p.info.is_owner}
                for p in instance.local_picker.peers()
            ],
            "region": [
                {"address": p.info.address, "datacenter": p.info.datacenter}
                for p in instance.region_picker.peers()
            ],
        }

    pls = getattr(instance, "peerlink_service", None)
    if pls is not None:
        # wire contract v2 occupancy (docs/wire.md): negotiated versions
        # per outbound link plus the server side's partial-post counters —
        # pending_replies at idle is the reassembly-leak probe
        wire = dict(pls.wire_debug())
        all_peers = getattr(instance, "all_peer_clients", None)
        if callable(all_peers):
            wire["peer_versions"] = {
                p.info.address: p.link_wire_version()
                for p in all_peers()
                if hasattr(p, "link_wire_version")
            }
        out["wire"] = wire

    prof = getattr(instance, "profiler", None)
    if prof is not None:
        out["profile"] = prof.debug()
    else:
        # the section is promised (v4) even on stub wirings with no
        # profiler — a disabled, empty shape keeps consumers branch-free
        out["profile"] = {"enabled": False, "phases": {}, "shares": {},
                          "lock_sites": 0, "captures": 0}

    led = getattr(instance, "ledger", None)
    if led is not None:
        out["ledger"] = led.debug()
    else:
        # the section is promised (v5) even on stub wirings with no
        # ledger — a disabled, empty shape keeps consumers branch-free
        out["ledger"] = {"enabled": False, "authorities": [], "admits": {},
                         "attempted": 0, "rejected": 0, "minted_budget": 0,
                         "windows_rolled": 0, "violations": 0,
                         "overshoot": {}, "keys_tracked": 0,
                         "pending_windows": 0, "audits": 0}

    ap = getattr(instance, "autopilot", None)
    if ap is not None:
        out["autopilot"] = ap.debug()
    else:
        # the section is promised (v6) even on stub wirings with no
        # autopilot — a disabled, empty shape keeps consumers branch-free
        out["autopilot"] = {"enabled": False, "frozen": False,
                            "freeze_reason": None, "ticks": 0, "moves": 0,
                            "clamps": 0, "freezes": 0, "frozen_drops": 0,
                            "controllers": {}}

    tracer = getattr(instance, "tracer", None)
    if tracer is not None:
        out["trace"] = {"sample": tracer.sample, "slow_ms": tracer.slow_ms,
                        **tracer.stats}

    lm = getattr(instance, "leases", None)
    if lm is not None and lm.enabled:
        out["leases"] = lm.debug()

    rm = getattr(instance, "reshard", None)
    if rm is not None:
        out["reshard"] = rm.debug()

    cg = getattr(instance, "collective_global", None)
    if cg is not None:
        out["collective_global"] = dict(cg.stats)
    mr = getattr(instance, "multiregion_manager", None)
    if mr is not None and getattr(mr, "stats", None):
        out["multiregion"] = dict(mr.stats)

    rec = getattr(instance, "recorder", None)
    if rec is not None:
        out["flight_recorder"] = rec.debug()
    an = getattr(instance, "anomaly", None)
    if an is not None:
        out["anomaly"] = an.debug()
    hist = getattr(instance, "history", None)
    if hist is not None:
        out["history"] = hist.debug()
    carto = getattr(instance, "keyspace", None)
    if carto is not None:
        out["keyspace"] = carto.debug()
    bw = getattr(instance, "bundle_writer", None)
    if bw is not None:
        out["bundles"] = bw.debug()
    de = getattr(instance, "deadline_expired_stats", None)
    if de:
        out["deadline_expired"] = dict(de)
    return out

"""Pin the /v1/debug/vars shape: the snapshot carries a schema_version,
and the section names consumers key on stay stable.

The schema is subset-stable — sections appear only when their subsystem
is wired, and ADDING a section is not a version bump. What this test
enforces: (a) the version field exists and matches the source constant;
(b) no known section silently disappears or gets renamed without the
version moving. Renaming a section => bump DEBUG_VARS_SCHEMA_VERSION and
update SECTIONS here, consciously.

v2 additionally promises the "history" and "keyspace" sections on every
Instance (the cartography plane is always constructed, even when its
tickers are disabled), and pins the /v1/debug/history and
/v1/debug/keyspace endpoint bodies.

v3 promises the "reshard" section on every Instance (the handoff plane
is always constructed; its "enabled" flag tracks GUBER_RESHARD).

v4 promises the "profile" section on every Instance (the serving-cycle
profiler is always constructed; its "enabled" flag tracks
GUBER_PROFILE), and pins the /v1/debug/profile and /v1/debug/kernels
endpoint bodies.

v5 promises the "ledger" section on every Instance (the decision ledger
& conservation audit plane is always constructed; its "enabled" flag
tracks GUBER_LEDGER), and pins the /v1/debug/ledger endpoint body.
History moves to v3 alongside: samples carry the cumulative
ledger_violations / ledger_overshoot_hits / ledger_minted_budget
columns.

v6 promises the "autopilot" section on every Instance (the bounded
closed-loop control plane is always constructed; its "enabled" flag
tracks GUBER_AUTOPILOT), with per-controller state (engaged/armed/
dwelling, last move, knob bands) and the move/clamp/freeze counters.
"""

import pytest

from gubernator_tpu.models.engine import Engine
from gubernator_tpu.obs.history import HISTORY_SCHEMA_VERSION
from gubernator_tpu.obs.introspect import DEBUG_VARS_SCHEMA_VERSION, debug_vars
from gubernator_tpu.obs.keyspace import KEYSPACE_SCHEMA_VERSION
from gubernator_tpu.obs.ledger import LEDGER_SCHEMA_VERSION
from gubernator_tpu.obs.profile import (KERNELS_SCHEMA_VERSION,
                                        PROFILE_SCHEMA_VERSION)
from gubernator_tpu.service.config import InstanceConfig
from gubernator_tpu.service.instance import Instance
from gubernator_tpu.types import PeerInfo

# every section name the snapshot may carry, by wiring condition
ALWAYS = {"schema_version", "advertise_address", "engine", "combiner",
          "kernel", "peers", "global", "flight_recorder", "anomaly",
          "history", "keyspace", "reshard", "profile", "ledger",
          "autopilot"}
OPTIONAL = {"wire", "trace", "leases", "collective_global", "multiregion",
            "bundles", "deadline_expired"}
SECTIONS = ALWAYS | OPTIONAL


@pytest.fixture
def instance():
    inst = Instance(InstanceConfig(backend=Engine(capacity=256)),
                    advertise_address="127.0.0.1:9999")
    inst.set_peers([PeerInfo(address="127.0.0.1:9999")])
    yield inst
    inst.close()


def test_schema_version_pinned(instance):
    dv = debug_vars(instance)
    assert dv["schema_version"] == DEBUG_VARS_SCHEMA_VERSION == 6


def test_always_sections_present(instance):
    dv = debug_vars(instance)
    missing = ALWAYS - set(dv)
    assert not missing, f"debug_vars lost sections: {sorted(missing)}"


def test_no_unknown_sections(instance):
    # a NEW section is fine to add — add it to OPTIONAL here so the name
    # is recorded as part of the contract; an unlisted one fails loudly
    dv = debug_vars(instance)
    unknown = set(dv) - SECTIONS
    assert not unknown, (
        f"debug_vars grew undeclared sections {sorted(unknown)}; add them "
        "to tests/test_debug_schema.py SECTIONS (and bump "
        "DEBUG_VARS_SCHEMA_VERSION only if an existing section changed)"
    )


def test_engine_section_names_the_device(instance):
    """Platform, device kind and count, table bytes per device, donation
    and key directory: what the daemon's boot line prints, where the
    backend is already reported (utils/platform.py device_facts)."""
    dev = debug_vars(instance)["engine"]["device"]
    assert {"platform", "device_kind", "device_count",
            "visible_device_count", "devices", "table_bytes_per_device",
            "table_layout", "donation", "key_directory", "memory",
            "compiles"} <= set(dev)
    assert dev["platform"] == "cpu"
    # the two live facts: allocator memory per device (nulls on the CPU)
    # and the compiles since Ready (nulls outside a daemon)
    assert [set(m) for m in dev["memory"]] == [
        {"device", "bytes_in_use", "peak_bytes_in_use", "bytes_limit"}]
    assert set(dev["compiles"]) == {"count", "seconds"}
    assert dev["table_bytes_per_device"] == [256 * 64]
    assert dev["table_layout"] == "u32[C,16]"


def test_engine_stats_say_why_a_launch_left_the_lean_lane(instance):
    """`engine.stats` carries `lean_tuples` and one `lean_refused_<reason>`
    a reason of ops/decide.py LEAN_REFUSALS on `Engine` (docs/
    observability.md "Why is my deployment off the lean lane"); integers,
    zero before any launch. `ShardedEngine` keeps neither (its funnel
    counts `lean_windows`), and the benchmark's readers give None there
    (benchmarks/onehit_math.py)."""
    from gubernator_tpu.obs.introspect import _backend_vars
    from gubernator_tpu.ops.decide import LEAN_REFUSALS
    from gubernator_tpu.parallel import ShardedEngine

    stats = debug_vars(instance)["engine"]["stats"]
    lean = {k: v for k, v in stats.items() if k.startswith("lean_")}
    assert lean == {"lean_tuples": 0,
                    **{"lean_refused_" + why: 0 for why in LEAN_REFUSALS}}
    assert LEAN_REFUSALS == ("capacity", "hits", "gregorian", "range",
                             "tuples")
    mesh = ShardedEngine(n_shards=2, capacity_per_shard=256, min_width=8,
                         max_width=8)
    try:
        assert not [k for k in _backend_vars(mesh)["stats"]
                    if k.startswith("lean_refused") or k == "lean_tuples"]
    finally:
        mesh.close()


def test_flight_recorder_and_anomaly_shapes(instance):
    dv = debug_vars(instance)
    assert {"enabled", "capacity", "size", "dropped",
            "counts"} <= set(dv["flight_recorder"])
    assert {"interval_s", "checks", "active", "trips", "slo", "burn_fast",
            "burn_slow"} <= set(dv["anomaly"])


def test_reshard_var_shape(instance):
    dv = debug_vars(instance)
    rs = dv["reshard"]
    assert {"enabled", "active", "ttl_s", "chunk_rows", "grace_s",
            "planning", "stats", "sessions", "recent"} <= set(rs)
    assert rs["enabled"] is False  # GUBER_RESHARD unset in tier-1
    assert rs["active"] is False
    assert rs["sessions"] == []


def test_history_and_keyspace_var_shapes(instance):
    dv = debug_vars(instance)
    assert {"enabled", "tick_s", "retention_s", "samples", "span_s",
            "ticks"} <= set(dv["history"])
    assert {"enabled", "interval_s", "top_k", "harvests",
            "errors"} <= set(dv["keyspace"])


def test_history_endpoint_schema_pinned(instance):
    body = instance.history.endpoint_body()
    assert body["schema_version"] == HISTORY_SCHEMA_VERSION == 3
    assert set(body) == {"schema_version", "enabled", "tick_s",
                         "retention_s", "sample_count", "samples"}
    instance.history.tick()
    sample = instance.history.endpoint_body()["samples"][-1]
    # the signal set consumers plot; adding a signal is fine, losing or
    # renaming one breaks every dashboard reading the ring
    assert {"t", "wall", "decisions", "over_limit", "deadline_expired",
            "sheds", "admission_pending", "pull_boundary_stalls",
            "lease_fail_close", "lease_outstanding", "lease_held_keys",
            "key_count", "evictions", "global_hits_depth",
            "global_broadcast_depth", "circuits_open", "slo_total",
            "slo_good", "slo_errors",
            # v2: the profiling-plane columns profile_shift diffs
            "profile_queue_wait_s", "profile_lock_wait_s",
            "profile_prep_s", "profile_dispatch_s",
            "profile_readback_s", "profile_demux_s",
            "profile_cycles",
            # v3: the conservation-audit columns bundles diff
            "ledger_violations", "ledger_overshoot_hits",
            "ledger_minted_budget"} <= set(sample)


def test_ledger_var_shape(instance):
    dv = debug_vars(instance)
    led = dv["ledger"]
    assert {"enabled", "authorities", "admits", "attempted", "rejected",
            "minted_budget", "windows_rolled", "violations", "overshoot",
            "keys_tracked", "pending_windows", "audits"} <= set(led)
    assert led["enabled"] is True  # GUBER_LEDGER unset => on
    assert led["authorities"] == ["owner", "lease", "degraded", "reshard",
                                  "global_cache"]


def test_ledger_endpoint_schema_pinned(instance):
    body = instance.ledger.endpoint_body()
    assert body["schema_version"] == LEDGER_SCHEMA_VERSION == 3
    assert set(body) == {"schema_version", "enabled", "authorities",
                         "totals", "overshoot", "recent_violations",
                         "ground_truth"}
    assert set(body["totals"]) == {
        "admits", "admits_other", "attempted", "rejected", "minted_budget",
        "windows_rolled", "violations", "overshoot_hits", "max_overshoot",
        "keys_tracked", "key_overflow", "pending_windows",
        "pending_dropped", "unattributed_hits", "audits",
        "slots_asked", "slots_resolved", "slots_named", "lanes_folded"}
    assert set(body["overshoot"]) == {"n", "total_hits", "max_hits",
                                      "p50_hits", "p99_hits"}
    assert set(body["ground_truth"]) == {"keys_checked", "ledger_hits",
                                         "device_hits", "breaches"}


def test_autopilot_var_shape(instance):
    dv = debug_vars(instance)
    ap = dv["autopilot"]
    assert {"enabled", "frozen", "freeze_reason", "ticks", "moves",
            "clamps", "freezes", "frozen_drops",
            "controllers"} <= set(ap)
    assert ap["enabled"] is False  # GUBER_AUTOPILOT unset => off
    assert ap["frozen"] is False
    # per-controller shape: the four controllers are always declared,
    # each with its hysteresis state and per-knob bands
    assert set(ap["controllers"]) == {"admission", "hotkey", "capacity",
                                      "pipeline"}
    for ctl in ap["controllers"].values():
        assert {"engaged", "armed", "dwelling", "signal", "value",
                "trip", "clear", "knobs", "last_move"} <= set(ctl)
        for knob in ctl["knobs"].values():
            assert {"baseline", "floor", "ceiling", "step",
                    "moves"} <= set(knob)


def test_profile_var_shape(instance):
    dv = debug_vars(instance)
    prof = dv["profile"]
    assert {"enabled", "phases", "shares", "lock_sites",
            "captures"} <= set(prof)
    assert prof["enabled"] is True  # GUBER_PROFILE unset => on


def test_profile_endpoint_schema_pinned(instance):
    body = instance.profiler.endpoint_body()
    assert body["schema_version"] == PROFILE_SCHEMA_VERSION == 4
    assert set(body) == {"schema_version", "enabled", "phases", "front",
                         "lock_sites", "lock_hold_sites", "bg_sites",
                         "decomposition", "recent", "capture"}
    # the phase taxonomy dashboards key on; renaming a phase is a
    # schema_version bump, not a silent drift
    taxonomy = {"queue_wait", "lock_wait", "prep", "dispatch",
                "readback", "demux"}
    # v2: the native front's own histograms ride beside the cycle's
    # phases, outside the decomposition (a frame waits while other
    # windows run), with its counters in `front`
    front = {"front_wait", "front_call", "front_parse", "front_write"}
    # v3: `leftover`, a pull worker's stretch inside _leftover_items; it
    # holds whole cycles of other threads, so it too stands outside the
    # decomposition
    paths = {"leftover"}
    # v4: what `dispatch` and `readback` are made of, stamped in the
    # engines' launch and fetch funnels, and the engine lock's holds (per
    # site in `lock_hold_sites`); outside the decomposition too, so its
    # shares read what they read in v3
    parts = {"stage", "launch", "device_wait", "fetch", "lock_hold"}
    assert set(body["phases"]) == taxonomy | front | paths | parts
    for block in ("lock_sites", "lock_hold_sites"):
        for snap in body[block].values():
            assert {"n", "total_ns", "max_ns", "p50_ns",
                    "p99_ns"} == set(snap)
    assert set(body["decomposition"]) == taxonomy
    assert set(body["front"]) == {"attached", "pulls", "frames_pulled",
                                  "items_pulled", "frames_native"}
    for snap in body["bg_sites"].values():
        assert {"n", "total_ns", "max_ns", "p50_ns", "p99_ns"} == set(snap)
    for snap in body["phases"].values():
        assert {"n", "total_ns", "max_ns", "p50_ns", "p99_ns"} == set(snap)
    for d in body["decomposition"].values():
        assert {"count", "total_s", "avg_us", "share"} == set(d)
    assert {"count", "min_interval_s", "last_path", "last_mode",
            "last_rates", "options"} == set(body["capture"])


def test_kernels_endpoint_schema_pinned(instance):
    from gubernator_tpu.ops.decide import kernel_telemetry

    body = kernel_telemetry.kernels_body()
    # v2: `lanes_total` went (nothing ever fed it)
    assert body["schema_version"] == KERNELS_SCHEMA_VERSION == 2
    assert set(body) == {"schema_version", "kernels"}
    for rec in body["kernels"].values():
        assert {"windows", "dispatch_ns", "cost"} == set(rec)


def test_keyspace_endpoint_schema_pinned(instance):
    body = instance.keyspace.endpoint_body()
    assert body["schema_version"] == KEYSPACE_SCHEMA_VERSION == 1
    assert set(body) == {"schema_version", "enabled", "interval_s",
                         "top_k", "report", "forecast"}
    rep = body["report"]
    assert rep is not None  # first endpoint_body triggers a harvest
    assert {"schema_version", "captured_at", "backend", "keys_resolvable",
            "occupancy", "evictions", "hbm", "hit_mass", "top_keys",
            "harvest_ms"} <= set(rep)
    assert {"key_count", "capacity", "fill_fraction",
            "free_slots"} == set(rep["occupancy"])
    fc = body["forecast"]
    assert {"projectable", "capacity", "pressure_fraction", "samples",
            "span_s", "key_count", "fill_fraction", "growth_keys_per_s",
            "eviction_rate_per_s", "time_to_full_s",
            "time_to_pressure_s"} == set(fc)

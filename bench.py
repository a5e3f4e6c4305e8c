"""Headline benchmark: rate-limit decisions/sec on one chip at 10M active keys.

Measures the steady-state throughput of the batched decision kernel
(ops/decide.py) against a 10M-slot key table resident in HBM — the TPU-native
replacement for the reference's per-request bucket state machines
(reference: algorithms.go:24-336, production headline >2,000 req/s/node,
README.md:94-100; see BASELINE.md).

Measurements, all on device-resident request windows (the serving tier's
own numbers — gRPC, batching, host prep — live in scripts/bench_suite.py):

- headline: sustained throughput with backlog coalescing — the engine's
  decide_scan_packed retires K=128 windows per dispatch (the serving engine
  uses the same path at depth 32 to retire duplicate-key rounds in one
  launch — _MAX_SCAN bounds window latency);
- extras: one-window-per-dispatch throughput, synchronous per-window
  latency p50/p99 (incl. readback), and the dispatch-only enqueue rate.

EVERY timed section ends on a data-dependent fetch of the result, so a
timing can never be the enqueue rate. The enqueue-only rate is reported
alongside as a diagnostic. Not yet run on an attached chip (chip_smoke.py
is the only on-chip record so far).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

REFERENCE_BASELINE_RPS = 2_000.0  # reference production node (README.md:94-100)
METRIC = "rate-limit decisions/sec/chip @ 10M active keys"
UNIT = "decisions/s"
TABLE_CAPACITY = 10_000_000  # north-star active key count (BASELINE.json)
BATCH_WIDTH = 8_192  # one aggregated batch window (the engine's max_width
# design point)
SCAN_K = 128  # windows retired per dispatch; at this depth the host can't
# outrun the device — per-call wall time stops growing with K, so the
# deeper scan amortizes launch overhead ~4x vs the engine's serving-path
# default of 32 (_MAX_SCAN, which stays smaller to bound window latency)
N_VARIANTS = 4
TARGET_SECONDS = 3.0


# extra phases that raised: each is reported in its row AND makes main()
# exit non-zero after the JSON line is printed
_PHASE_FAILURES: list = []


def _init_watchdog(seconds: float = 180.0):
    """Backend init that never returns must not hang the harness: say so
    and exit non-zero."""
    import os
    import threading

    def fire():
        print(f"no device backend within {seconds:.0f} s",
              file=sys.stderr, flush=True)
        os._exit(3)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


def phase_breakdown() -> dict:
    """Phase split of the FULL serving stack, measured by the tracing tier
    itself (obs/trace.py): a 2-instance loopback cluster forwards singles
    non-owner -> owner at sample rate 1.0 and the recorded spans give each
    phase's latency — ingress (whole request at the non-owner), peer.hop
    (forward RPC incl. the micro-batch window), owner.apply, combiner.wait
    and kernel.dispatch (owner side). This is the combiner/kernel/peer-hop
    split the BENCH_*.json trajectory tracks per PR; absolute numbers are
    rig-dependent (loopback gRPC + this platform's dispatch latency), the
    RATIOS are the regression signal."""
    import numpy as np

    from gubernator_tpu.models.engine import Engine
    from gubernator_tpu.service.config import BehaviorConfig, InstanceConfig
    from gubernator_tpu.service.convert import req_to_pb
    from gubernator_tpu.service.grpc_api import close_channels, dial_v1
    from gubernator_tpu.service.instance import Instance
    from gubernator_tpu.service.pb import gubernator_pb2 as pb
    from gubernator_tpu.service.server import make_server
    from gubernator_tpu.obs.trace import Tracer
    from gubernator_tpu.types import PeerInfo, RateLimitReq

    N_REQ = 40
    nodes = []
    try:
        behaviors = BehaviorConfig(batch_wait_s=0.001, peer_link_offset=0)
        for _ in range(2):
            # one width bucket, no warmup: the handful of inline compiles
            # land on the first requests and fall out of the p50s
            eng = Engine(capacity=1024, min_width=64, max_width=64)
            inst = Instance(
                InstanceConfig(behaviors=behaviors, backend=eng,
                               tracer=Tracer(sample=1.0)),
                advertise_address="pending")
            server, port = make_server(inst, "127.0.0.1:0")
            inst.advertise_address = f"127.0.0.1:{port}"
            server.start()
            nodes.append((inst, server))
        infos = [PeerInfo(address=i.advertise_address) for i, _ in nodes]
        for inst, _ in nodes:
            inst.set_peers(infos)

        # send from whichever node does NOT own the key, forcing the hop
        key = "bk0"
        owner_addr = nodes[0][0].get_peer(
            RateLimitReq(name="ph", unique_key=key).hash_key()).info.address
        non_owner = next(inst for inst, _ in nodes
                         if inst.advertise_address != owner_addr)
        stub = dial_v1(non_owner.advertise_address)
        msg = pb.GetRateLimitsReq(requests=[req_to_pb(RateLimitReq(
            name="ph", unique_key=key, hits=1, limit=1 << 20,
            duration=3_600_000))])
        for _ in range(N_REQ):
            stub.GetRateLimits(msg, timeout=30)
        phases: dict = {}
        for inst, _ in nodes:
            for spans in inst.tracer.traces().values():
                for s in spans:
                    phases.setdefault(s["name"], []).append(s["duration_ms"])
        return {
            name: {
                "p50_ms": round(float(np.percentile(v, 50)), 4),
                "p99_ms": round(float(np.percentile(v, 99)), 4),
                "n": len(v),
            }
            for name, v in sorted(phases.items())
        }
    finally:
        for inst, server in nodes:
            server.stop(grace=0.2)
            close_channels(inst.advertise_address)
            inst.close()


def _obs_bench(n_calls: int = 1500, batch: int = 64, reps: int = 3) -> dict:
    """Observability-plane overhead on the serving path: the SAME
    single-node Instance serving identical batch streams with the flight
    recorder enabled vs GUBER_FLIGHT_RECORDER=0 (the escape hatch turns
    emit() into one attribute test). The anomaly engine's observe() runs
    on both sides — it IS the always-on plane; what the hatch removes is
    the recorder. The flag alternates every CHUNK calls within one pass
    (shared-CPU drift between coarse reps dwarfs the cost under test;
    fine interleaving lands both sides in the same drift regime);
    acceptance is overhead <= 2%.

    Steady-state serving emits no events (recorder kinds are rare state
    EDGES — circuit flips, brownout enter/exit, queue high-water), so
    this measures the per-batch fixed cost: the enabled check, the
    anomaly feed, and the wrapper bookkeeping. A per-sweep timing for
    the detector pass rides along informationally."""
    from gubernator_tpu.models.engine import Engine
    from gubernator_tpu.service.config import InstanceConfig
    from gubernator_tpu.service.instance import Instance
    from gubernator_tpu.types import PeerInfo, RateLimitReq

    inst = Instance(InstanceConfig(backend=Engine(capacity=262_144)),
                    advertise_address="127.0.0.1:1")
    inst.set_peers([PeerInfo(address="127.0.0.1:1")])  # self-owned: no RPC
    frames = [
        [RateLimitReq(name="obsbench", unique_key=f"k{(i * batch + j) % 4096}",
                      hits=1, limit=1 << 30, duration=3_600_000)
         for j in range(batch)]
        for i in range(n_calls)
    ]
    try:
        for f in frames[:100]:  # compile + warm the width bucket
            inst.get_rate_limits(f)

        import gc
        import statistics

        CHUNK = 25
        elapsed = {True: 0.0, False: 0.0}
        calls = {True: 0, False: 0}
        pair_overheads = []  # per adjacent on/off pair: scheduler
        # hiccups land in single chunks; the median ignores them
        gc_was_enabled = gc.isenabled()
        gc.disable()
        try:
            for rep in range(reps):
                i = 0
                while i + 2 * CHUNK <= n_calls:
                    first = len(pair_overheads) % 2 == 0
                    rate = {}
                    for enabled in (first, not first):
                        inst.recorder.enabled = enabled
                        chunk = frames[i:i + CHUNK]
                        i += CHUNK
                        t0 = time.perf_counter()
                        for f in chunk:
                            inst.get_rate_limits(f)
                        dt = time.perf_counter() - t0
                        elapsed[enabled] += dt
                        calls[enabled] += CHUNK
                        rate[enabled] = CHUNK * batch / dt
                    pair_overheads.append(
                        (rate[False] - rate[True]) / rate[False])
        finally:
            if gc_was_enabled:
                gc.enable()
        inst.recorder.enabled = True
        on = calls[True] * batch / elapsed[True]
        off = calls[False] * batch / elapsed[False]
        overhead_pct = statistics.median(pair_overheads) * 100.0

        t0 = time.perf_counter()
        sweeps = 50
        for _ in range(sweeps):
            inst.anomaly.check(now=time.monotonic())
            time.sleep(0.02)  # past the sweep-coalescing guard
        sweep_us = ((time.perf_counter() - t0) / sweeps - 0.02) * 1e6

        return {
            "observability": {
                "recorder_on_decisions_per_sec": round(on, 1),
                "recorder_off_decisions_per_sec": round(off, 1),
                # positive = the enabled recorder costs throughput;
                # median over on/off chunk pairs, hiccup-robust
                "overhead_pct": round(overhead_pct, 2),
                "chunk_pairs": len(pair_overheads),
                "anomaly_sweep_us": round(max(sweep_us, 0.0), 1),
                "slo_batches_observed": inst.anomaly.debug()["slo"]["total"],
                "reps": reps,
                "batch": batch,
                "calls_per_rep": n_calls,
            }
        }
    finally:
        inst.close()


def _cartography_bench(n_calls: int = 1200, batch: int = 64,
                       reps: int = 3) -> dict:
    """Cartography-plane overhead on the serving path: the SAME
    single-node Instance serving identical batch streams with the
    metrics-history tick running in-band once per chunk vs the
    GUBER_HISTORY=0 hatch (which turns the scrape piggyback into one
    attribute test). One tick per ~5 ms chunk is ~1000x the production
    5 s cadence, so the interleaved pct is a stress ceiling; the number
    the <= 2% budget is judged on is amortized_overhead_pct — per-op
    tick/harvest cost duty-cycled at the production cadence (5 s tick,
    60 s harvest). The flag alternates every CHUNK calls within one
    pass, same drift-regime rationale as _obs_bench.

    The keyspace harvest reads the device hit-counter column and
    resolves top-K off the serving path; it is timed separately
    (harvest_ms) because even one harvest per chunk would dominate a
    5 ms chunk and measure cadence, not cost."""
    from gubernator_tpu.models.engine import Engine
    from gubernator_tpu.service.config import InstanceConfig
    from gubernator_tpu.service.instance import Instance
    from gubernator_tpu.types import PeerInfo, RateLimitReq

    HIST_TICK_PROD_S = 5.0
    HARVEST_PROD_S = 60.0
    inst = Instance(InstanceConfig(backend=Engine(capacity=262_144),
                                   history_tick_s=1e-4,  # every tick records
                                   keyspace_interval_s=3600.0),
                    advertise_address="127.0.0.1:1")
    inst.set_peers([PeerInfo(address="127.0.0.1:1")])  # self-owned: no RPC
    frames = [
        [RateLimitReq(name="cartobench", unique_key=f"k{(i * batch + j) % 4096}",
                      hits=1, limit=1 << 30, duration=3_600_000)
         for j in range(batch)]
        for i in range(n_calls)
    ]
    try:
        for f in frames[:100]:  # compile + warm the width bucket
            inst.get_rate_limits(f)

        import gc
        import statistics

        CHUNK = 25
        elapsed = {True: 0.0, False: 0.0}
        calls = {True: 0, False: 0}
        pair_overheads = []  # median over adjacent on/off pairs
        gc_was_enabled = gc.isenabled()
        gc.disable()
        try:
            for rep in range(reps):
                i = 0
                while i + 2 * CHUNK <= n_calls:
                    first = len(pair_overheads) % 2 == 0
                    rate = {}
                    for ticking in (first, not first):
                        chunk = frames[i:i + CHUNK]
                        i += CHUNK
                        t0 = time.perf_counter()
                        for f in chunk:
                            inst.get_rate_limits(f)
                        if ticking:  # the scrape piggyback's real work
                            inst.history.tick()
                        dt = time.perf_counter() - t0
                        elapsed[ticking] += dt
                        calls[ticking] += CHUNK
                        rate[ticking] = CHUNK * batch / dt
                    pair_overheads.append(
                        (rate[False] - rate[True]) / rate[False])
        finally:
            if gc_was_enabled:
                gc.enable()
        on = calls[True] * batch / elapsed[True]
        off = calls[False] * batch / elapsed[False]
        overhead_pct = statistics.median(pair_overheads) * 100.0

        # per-op costs, timed directly for the production-cadence duty
        # cycle; synthetic timestamps defeat the tick gate so every
        # iteration pays the full collect+record path, not the no-op
        tick_costs = []
        base = time.monotonic()
        for j in range(200):
            t0 = time.perf_counter()
            s = inst.history.collect(base + float(j))
            inst.history.record(base + float(j), s)
            tick_costs.append(time.perf_counter() - t0)
        tick_us = statistics.median(tick_costs) * 1e6
        harvest_costs = []
        for _ in range(10):
            t0 = time.perf_counter()
            inst.keyspace.harvest(now=time.monotonic())
            harvest_costs.append(time.perf_counter() - t0)
        harvest_ms = statistics.median(harvest_costs) * 1e3
        amortized_pct = 100.0 * (tick_us * 1e-6 / HIST_TICK_PROD_S
                                 + harvest_ms * 1e-3 / HARVEST_PROD_S)

        rep_ks = inst.keyspace.last_report() or {}
        return {
            "cartography": {
                "ticker_on_decisions_per_sec": round(on, 1),
                "ticker_off_decisions_per_sec": round(off, 1),
                # in-band tick once per chunk (~1000x production cadence):
                # a stress ceiling, positive = ticking costs throughput
                "overhead_pct": round(overhead_pct, 2),
                # per-op cost duty-cycled at 5 s tick / 60 s harvest —
                # the number judged against the <= 2% budget
                "amortized_overhead_pct": round(amortized_pct, 4),
                "tick_us": round(tick_us, 1),
                "harvest_ms": round(harvest_ms, 3),
                "table_capacity": 262_144,
                "keys_harvested": (rep_ks.get("occupancy") or {}).get(
                    "key_count"),
                "chunk_pairs": len(pair_overheads),
                "history_samples": inst.history.sample_count(),
                "reps": reps,
                "batch": batch,
                "calls_per_rep": n_calls,
            }
        }
    finally:
        inst.close()


def _capture_bench(n_calls: int = 800, batch: int = 64,
                   reps: int = 3) -> dict:
    """Traffic-shape capture cost against the 2% observability budget.
    capture_trace() is a pure read of the history ring + cartographer +
    recorder, normally triggered by an operator hitting
    /v1/debug/capture — it is NOT on the serving path. Measured two
    ways, mirroring _cartography_bench: an in-band capture once per
    chunk (a stress ceiling ~orders beyond any real cadence) and the
    direct per-capture cost duty-cycled at a one-capture-per-minute
    operator cadence, which is the number judged against the budget."""
    from gubernator_tpu.models.engine import Engine
    from gubernator_tpu.obs.capture import capture_trace
    from gubernator_tpu.service.config import InstanceConfig
    from gubernator_tpu.service.instance import Instance
    from gubernator_tpu.types import PeerInfo, RateLimitReq

    CAPTURE_PROD_S = 60.0
    inst = Instance(InstanceConfig(backend=Engine(capacity=262_144),
                                   history_tick_s=1e-4,
                                   keyspace_interval_s=3600.0),
                    advertise_address="127.0.0.1:1")
    inst.set_peers([PeerInfo(address="127.0.0.1:1")])  # self-owned: no RPC
    frames = [
        [RateLimitReq(name="capbench", unique_key=f"k{(i * batch + j) % 4096}",
                      hits=1, limit=1 << 30, duration=3_600_000)
         for j in range(batch)]
        for i in range(n_calls)
    ]
    try:
        t_ring = time.monotonic()
        for f in frames[:100]:  # compile + warm the width bucket
            inst.get_rate_limits(f)
            # give the capture a real ring to read: the ring floors
            # tick_s at 50 ms, so sub-ms warm frames must stamp
            # synthetic tick times to land as distinct samples
            t_ring += 0.1
            inst.history.tick(now=t_ring)
        inst.keyspace.harvest()

        import gc
        import statistics

        CHUNK = 25
        elapsed = {True: 0.0, False: 0.0}
        calls = {True: 0, False: 0}
        pair_overheads = []
        gc_was_enabled = gc.isenabled()
        gc.disable()
        try:
            for rep in range(reps):
                i = 0
                while i + 2 * CHUNK <= n_calls:
                    first = len(pair_overheads) % 2 == 0
                    rate = {}
                    for capturing in (first, not first):
                        chunk = frames[i:i + CHUNK]
                        i += CHUNK
                        t0 = time.perf_counter()
                        for f in chunk:
                            inst.get_rate_limits(f)
                        if capturing:
                            capture_trace(inst, n_events=64)
                        dt = time.perf_counter() - t0
                        elapsed[capturing] += dt
                        calls[capturing] += CHUNK
                        rate[capturing] = CHUNK * batch / dt
                    pair_overheads.append(
                        (rate[False] - rate[True]) / rate[False])
        finally:
            if gc_was_enabled:
                gc.enable()
        on = calls[True] * batch / elapsed[True]
        off = calls[False] * batch / elapsed[False]
        overhead_pct = statistics.median(pair_overheads) * 100.0

        costs = []
        trace = None
        for _ in range(50):
            t0 = time.perf_counter()
            trace = capture_trace(inst, n_events=256)
            costs.append(time.perf_counter() - t0)
        capture_ms = statistics.median(costs) * 1e3
        amortized_pct = 100.0 * capture_ms * 1e-3 / CAPTURE_PROD_S

        return {
            "capture": {
                "capture_on_decisions_per_sec": round(on, 1),
                "capture_off_decisions_per_sec": round(off, 1),
                # one in-band capture per ~5 ms chunk: a stress ceiling
                "overhead_pct": round(overhead_pct, 2),
                # per-capture cost duty-cycled at one capture per minute
                # — the number judged against the <= 2% budget
                "amortized_overhead_pct": round(amortized_pct, 4),
                "capture_ms": round(capture_ms, 3),
                "trace_segments": len(trace["history"]["segments"]),
                "trace_events": len(trace["events"]["tail"]),
                "derived_mean_rate_rps": trace["derived"]["mean_rate_rps"],
                "chunk_pairs": len(pair_overheads),
                "reps": reps,
                "batch": batch,
                "calls_per_rep": n_calls,
            }
        }
    finally:
        inst.close()


def _scenarios_bench(profile: str = "short", autopilot: bool = True) -> dict:
    """The scenario atlas as a bench section: every named scenario runs
    against its own fresh in-process cluster and records its verdict.
    verdict_pass is the hard bench_check gate (a scenario flipping
    PASS->FAIL across rounds is a regression, full stop); the latency
    and goodput numbers ride along as operating-point context. Each
    shape then re-runs GUBER_AUTOPILOT-armed on the same seed, keyed
    `<name>@autopilot` — gated by bench_check at the SAME zero
    tolerance (the closed-loop controllers are not allowed to be a
    flakiness excuse)."""
    from gubernator_tpu.scenarios import run_atlas

    atlas = run_atlas(profile=profile)
    rows = dict(atlas["scenarios"])
    if autopilot:
        armed = run_atlas(profile=profile, autopilot=True)
        rows.update({f"{name}@autopilot": v
                     for name, v in armed["scenarios"].items()})
    out = {}
    for name, v in rows.items():
        out[name] = {
            "verdict_pass": int(v["passed"]),
            "goodput": v["goodput"],
            "over_limit_share": v["over_limit_share"],
            "error_share": v["error_share"],
            "p50_ms": v["stats"]["latency_ms"]["p50"],
            "p99_ms": v["stats"]["latency_ms"]["p99"],
            "offered": v["stats"]["offered"],
            "detectors_tripped": sum(
                v["stats"]["detectors_tripped"].values()),
        }
    out["passed_count"] = sum(
        v["verdict_pass"] for v in out.values() if isinstance(v, dict))
    out["total"] = len(rows)
    return {"scenarios": out}


def _profile_bench(n_calls: int = 1500, batch: int = 64, reps: int = 3) -> dict:
    """Profiling-plane overhead on the serving path: the SAME single-node
    Instance serving identical batch streams with the serving-cycle
    profiler enabled vs the GUBER_PROFILE=0 hatch (which turns every
    observe()/lock_wait() into one attribute test before the clock is
    even read). The flag alternates every CHUNK calls within one pass,
    same drift-regime rationale as _obs_bench. Budget <= 2%; target 0.5%
    — the profiler is ~10 perf_counter_ns reads + histogram increments
    per engine window group, amortized over a whole batch.

    A directly-timed per-observe cost and the /v1/debug/profile body
    render time ride along informationally."""
    from gubernator_tpu.models.engine import Engine
    from gubernator_tpu.service.config import InstanceConfig
    from gubernator_tpu.service.instance import Instance
    from gubernator_tpu.types import PeerInfo, RateLimitReq

    inst = Instance(InstanceConfig(backend=Engine(capacity=262_144)),
                    advertise_address="127.0.0.1:1")
    inst.set_peers([PeerInfo(address="127.0.0.1:1")])  # self-owned: no RPC
    prof = inst.profiler
    frames = [
        [RateLimitReq(name="profbench", unique_key=f"k{(i * batch + j) % 4096}",
                      hits=1, limit=1 << 30, duration=3_600_000)
         for j in range(batch)]
        for i in range(n_calls)
    ]
    try:
        for f in frames[:100]:  # compile + warm the width bucket
            inst.get_rate_limits(f)

        import gc
        import statistics

        CHUNK = 25
        elapsed = {True: 0.0, False: 0.0}
        calls = {True: 0, False: 0}
        pair_overheads = []  # median over adjacent on/off pairs
        gc_was_enabled = gc.isenabled()
        gc.disable()
        try:
            for rep in range(reps):
                i = 0
                while i + 2 * CHUNK <= n_calls:
                    first = len(pair_overheads) % 2 == 0
                    rate = {}
                    for enabled in (first, not first):
                        prof.enabled = enabled
                        chunk = frames[i:i + CHUNK]
                        i += CHUNK
                        t0 = time.perf_counter()
                        for f in chunk:
                            inst.get_rate_limits(f)
                        dt = time.perf_counter() - t0
                        elapsed[enabled] += dt
                        calls[enabled] += CHUNK
                        rate[enabled] = CHUNK * batch / dt
                    pair_overheads.append(
                        (rate[False] - rate[True]) / rate[False])
        finally:
            if gc_was_enabled:
                gc.enable()
        prof.enabled = True
        on = calls[True] * batch / elapsed[True]
        off = calls[False] * batch / elapsed[False]
        overhead_pct = statistics.median(pair_overheads) * 100.0

        # per-observe cost, timed directly (informational)
        t0 = time.perf_counter()
        N_OBS = 20_000
        for j in range(N_OBS):
            prof.observe("prep", 1000 + j)
        observe_ns = (time.perf_counter() - t0) / N_OBS * 1e9
        # endpoint render cost (off the serving path, but a dashboard
        # polling it every second should know what it costs the node)
        t0 = time.perf_counter()
        for _ in range(50):
            body = prof.endpoint_body()
        endpoint_us = (time.perf_counter() - t0) / 50 * 1e6

        return {
            "profiler": {
                "profiler_on_decisions_per_sec": round(on, 1),
                "profiler_off_decisions_per_sec": round(off, 1),
                # positive = the enabled profiler costs throughput;
                # median over on/off chunk pairs, hiccup-robust.
                # budget <= 2%, target 0.5%
                "overhead_pct": round(overhead_pct, 2),
                "observe_ns": round(observe_ns, 1),
                "endpoint_body_us": round(endpoint_us, 1),
                "phases_observed": sorted(
                    p for p, t in prof.totals().items() if t["n"]),
                "lock_sites": sorted(body["lock_sites"]),
                "chunk_pairs": len(pair_overheads),
                "reps": reps,
                "batch": batch,
                "calls_per_rep": n_calls,
            }
        }
    finally:
        inst.close()


def _product_combiner_bench(eng, threads: int = 12, scan: int = 8,
                            subs_per_thread: int = 24) -> dict:
    """Serving throughput through the PRODUCT combiner path — not a
    bespoke loop: `threads` callers block in BackendCombiner.submit()
    with max-width request-object batches against the 10M-key engine.
    Completion is forced by construction (a future resolves only after
    its window's data-dependent readback). Returns the bench JSON rows."""
    import threading as _t

    from gubernator_tpu.service.combiner import BackendCombiner

    width = eng.max_width
    # request objects over keys resident in the 10M directory ("b_k%d")
    rng = np.random.RandomState(21)
    from gubernator_tpu.types import RateLimitReq

    variants = []
    for _ in range(threads):
        ids = rng.choice(TABLE_CAPACITY, width, replace=False)
        variants.append([
            RateLimitReq(name="b", unique_key="k%d" % i, hits=1,
                         limit=1 << 30, duration=3_600_000)
            for i in ids
        ])
    # compile the scan-group shapes up front, exactly as a daemon boots —
    # a cold compile inside a timed segment would poison the measurement
    eng.warmup_pipeline(max_group=scan)

    def run(depth: int, n_subs: int) -> float:
        c = BackendCombiner(eng, depth=depth, scan=scan)
        try:
            errs = []

            def caller(v):
                try:
                    for _ in range(n_subs):
                        resp = c.submit(v)
                        if resp[0].status not in (0, 1):
                            raise RuntimeError("bad status")
                except BaseException as e:  # noqa: BLE001
                    errs.append(e)

            ts = [_t.Thread(target=caller, args=(variants[i],), daemon=True)
                  for i in range(threads)]
            t0 = time.perf_counter()
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            elapsed = time.perf_counter() - t0
            if errs:
                raise errs[0]
            stats = c.stats
        finally:
            c.close()
        return threads * n_subs * width / elapsed, stats

    run(3, 2)  # warm the full path (combiner threads, demux, staging ring)
    probe = {}
    probe_stats = {}
    for depth in (1, 3, 6):
        rate, stats = run(depth, subs_per_thread)
        probe[depth] = round(rate, 1)
        probe_stats[depth] = stats
    best_depth = max(probe, key=probe.get)
    stats = probe_stats[best_depth]
    return {
        "product_combiner_decisions_per_sec": probe[best_depth],
        "product_combiner": {
            "scope": "BackendCombiner.submit() request objects -> "
                     f"RateLimitResp objects, {threads} callers x "
                     f"{width}-wide submissions, scan groups <= {scan} "
                     "windows/launch, keydir(10M resident)",
            "depth_probe_decisions_per_sec":
                {str(d): r for d, r in probe.items()},
            "depth": best_depth,
            "serial_decisions_per_sec": probe[1],
            "speedup_vs_serial": round(
                probe[best_depth] / max(probe[1], 1.0), 2),
            "pipelined_windows": stats["pipelined_windows"],
            "group_launches": stats["group_launches"],
            "fill_stalls": stats["fill_stalls"],
        },
    }


def _overload_bench(eng, budget_ms: float = 150.0, seconds: float = 3.0,
                    batch: int = 64, offered_x: float = 2.0) -> dict:
    """Overload discipline through a REAL single-node Instance (admission
    controller + deadline budgets + combiner dequeue shed), owner-local
    serving (BENCH_r08 acceptance row).

    First a closed-loop capacity probe, then open-loop offered load at
    ~`offered_x` that capacity in two modes: ADMISSION (every call carries
    a `budget_ms` deadline, GUBER_MAX_PENDING sized by Little's law to the
    budget — capacity x budget) vs the no-admission, no-budget BASELINE
    (PR 4 behavior: work queues unboundedly). Records goodput (decisions
    answered WITHIN budget per second), shed rate, and accepted-call
    p50/p99 — the claim under test is that shedding the excess beats
    queueing it: the admission run's accepted p99 stays near the service
    time while the baseline's grows with the backlog."""
    import threading as _t
    from concurrent.futures import ThreadPoolExecutor

    from gubernator_tpu.cluster.harness import test_behaviors
    from gubernator_tpu.service import deadline as deadline_mod
    from gubernator_tpu.service.config import InstanceConfig
    from gubernator_tpu.service.deadline import (
        AdmissionRejectedError,
        DeadlineExceededError,
    )
    from gubernator_tpu.service.instance import Instance
    from gubernator_tpu.types import PeerInfo, RateLimitReq

    behaviors = test_behaviors()
    behaviors.max_pending = 0
    inst = Instance(InstanceConfig(behaviors=behaviors, backend=eng),
                    advertise_address="bench-local")
    inst.set_peers([PeerInfo(address="bench-local")])  # all owner-local

    rng = np.random.RandomState(31)
    pool_keys = ["k%d" % i
                 for i in rng.choice(TABLE_CAPACITY, 4096, replace=False)]

    def make_batch(i: int):
        base = (i * 17) % (len(pool_keys) - batch)
        return [RateLimitReq(name="b", unique_key=k, hits=1, limit=1 << 30,
                             duration=3_600_000)
                for k in pool_keys[base:base + batch]]

    try:
        # warm the instance path AND make the whole key pool resident:
        # first-touch inserts are slower than steady-state hits, and a
        # capacity probe over cold keys would under-measure — "2x
        # capacity" would then not actually overload the warm open loop
        for start in range(0, len(pool_keys), batch):
            inst.get_rate_limits(
                [RateLimitReq(name="b", unique_key=k, hits=1,
                              limit=1 << 30, duration=3_600_000)
                 for k in pool_keys[start:start + batch]])

        def measure_capacity() -> float:
            # ---- closed-loop capacity probe ----------------------------
            # concurrency matches the open loop's client pool order: the
            # combiner merges concurrent calls into wider windows, so a
            # low-thread probe would UNDER-measure capacity and 2x
            # "offered" would not actually overload the node
            n_probe_threads, probe_s = 24, 1.5
            counts = [0] * n_probe_threads
            stop_at = time.perf_counter() + probe_s

            def probe_worker(ti: int) -> None:
                i = ti
                while time.perf_counter() < stop_at:
                    inst.get_rate_limits(make_batch(i))
                    counts[ti] += batch
                    i += n_probe_threads

            ts = [_t.Thread(target=probe_worker, args=(ti,), daemon=True)
                  for ti in range(n_probe_threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            return sum(counts) / probe_s  # decisions/s, closed loop

        capacity = measure_capacity()

        def open_loop(admission_on: bool) -> dict:
            behaviors.max_pending = (
                max(2 * batch, int(capacity * budget_ms / 1e3))
                if admission_on else 0)
            lock = _t.Lock()
            lat_ms, sheds = [], [0]

            def one(i: int) -> None:
                dl = (deadline_mod.capture(budget_ms)
                      if admission_on else None)
                token = deadline_mod.use(dl) if dl is not None else None
                t0 = time.perf_counter()
                try:
                    err = inst.get_rate_limits(make_batch(i))[0].error
                except (AdmissionRejectedError, DeadlineExceededError):
                    err = "SHED"
                finally:
                    if token is not None:
                        deadline_mod.reset(token)
                dt = (time.perf_counter() - t0) * 1e3
                with lock:
                    if err:
                        sheds[0] += 1
                    else:
                        lat_ms.append(dt)

            # burst dispatch on a coarse tick: per-call sleep pacing
            # cannot sustain the offered rate (sleep granularity alone
            # would throttle the generator below capacity)
            tick = 0.02
            per_tick = max(1, int(round(
                offered_x * capacity * tick / batch)))
            n_ticks = max(4, int(seconds / tick))
            n_offered = per_tick * n_ticks
            pool = ThreadPoolExecutor(max_workers=256)
            futs = []
            idx = 0
            t_start = time.perf_counter()
            for ti in range(n_ticks):
                delay = t_start + ti * tick - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                for _ in range(per_tick):
                    futs.append(pool.submit(one, 100 + idx))
                    idx += 1
            for f in futs:
                f.result()
            wall = time.perf_counter() - t_start
            pool.shutdown()
            good = [d for d in lat_ms if d <= budget_ms]
            pct = (lambda q: round(float(np.percentile(lat_ms, q)), 1)) \
                if lat_ms else (lambda q: None)
            return {
                "offered_calls": n_offered,
                "served_calls": len(lat_ms),
                "shed_calls": sheds[0],
                "shed_rate": round(sheds[0] / max(n_offered, 1), 3),
                "goodput_decisions_per_sec": round(
                    len(good) * batch / wall, 1),
                "accepted_p50_ms": pct(50),
                "accepted_p99_ms": pct(99),
                "max_pending": behaviors.max_pending,
            }

        # A shared-rig probe can land in a descheduled window and report
        # a fraction of the node's real capacity. Such a draw fails the
        # bench's own premise — "offered at 2x capacity" then does not
        # overload anything (shed rate 0, baseline p99 inside budget) and
        # the row measures the rig hiccup, not the overload discipline.
        # Detect that and retake the probe instead of recording it.
        attempts = 1
        while True:
            baseline = open_loop(admission_on=False)
            admission = open_loop(admission_on=True)
            # sheds are the unambiguous signature that offered load
            # actually exceeded capacity (a backlogged-baseline p99 can
            # spike on an under-measured probe too, so it proves nothing)
            if admission["shed_calls"] > 0 or attempts >= 3:
                break
            attempts += 1
            behaviors.max_pending = 0  # re-probe closed-loop, no admission
            capacity = measure_capacity()
    finally:
        inst.close()
    return {
        "overload": {
            "scope": "Instance.get_rate_limits owner-local, open-loop "
                     f"offered at {offered_x}x closed-loop capacity, "
                     f"{batch}-wide calls, budget {budget_ms:.0f} ms",
            "capacity_decisions_per_sec": round(capacity, 1),
            "offered_x": offered_x,
            "budget_ms": budget_ms,
            "probe_attempts": attempts,
            "baseline_no_admission": baseline,
            "admission": admission,
        },
    }


FRAME_WIDTH = 1024  # peerlink MAX_FRAME_ITEMS: the wire's frame cap


def _columnar_pipeline_bench(eng, scan: int = 8,
                             n_windows: int = 96) -> dict:
    """The zero-object columnar owner path (peerlink wire columns ->
    engine, no RateLimitReq/Resp objects), lock-step vs depth-N
    pipelined, on the same 10M-resident keydir working set.

    Lock-step is the pre-PR-3 serving loop (`submit_columnar` then
    `complete_columnar` per window — every readback blocks the next
    submit); the pipelined path launches scan groups of <= `scan`
    windows via launch_columnar_windows with `depth` group launches in
    flight and drains in dispatch order — exactly what
    service/peerlink.py _columnar_chunk now drives. Completion is
    forced by construction (a window's response columns fill only after
    its readback).

    The HEADLINE probe runs at the wire's frame granularity
    (MAX_FRAME_ITEMS = 1024 — the widest window a single client frame
    can carry, i.e. a GUBER_MAX_BATCH_WIDTH=1024-class deployment):
    there the lock-step loop pays one full dispatch per frame and the
    scan-grouped pipeline amortizes it across up to `scan` frames, which
    is the structural win this PR ships. A max-width (8192) row rides
    along: at that width the kernel dominates the cycle, so on a
    shared-core CPU rig the pipeline adds only its overlap margin (on a
    link-bound rig it is the BENCH_r05 2x regime)."""
    from collections import deque

    now = 1_700_000_000_000
    rng = np.random.RandomState(33)

    def make_variants(w, n_var):
        out = []
        for _ in range(n_var):
            ids = rng.choice(TABLE_CAPACITY, w, replace=False)
            ukeys = [b"k%d" % i for i in ids]
            keys = b"".join(b"b" + u for u in ukeys)
            off = np.zeros(w + 1, np.int32)
            np.cumsum([1 + len(u) for u in ukeys], out=off[1:])
            out.append((
                w, keys, off, np.ones(w, np.int32),
                np.ones(w, np.int64), np.full(w, 1 << 30, np.int64),
                np.full(w, 3_600_000, np.int64),
                np.zeros(w, np.int32), np.zeros(w, np.int32)))
        return out

    wc = [0]  # monotone now_ms cursor across every run

    def make_runners(w, variants):
        nv = len(variants)
        outs_pool = [[(np.zeros(w, np.int32), np.zeros(w, np.int64),
                       np.zeros(w, np.int64), np.zeros(w, np.int64))
                      for _ in range(scan)] for _ in range(8)]
        st, li, re, rs = outs_pool[0][0]

        def run_lockstep(k_windows):
            t0 = time.perf_counter()
            for i in range(k_windows):
                h = eng.submit_columnar(
                    *variants[(wc[0] + i) % nv], 0, now_ms=now + wc[0] + i)
                left = eng.complete_columnar(h, st, li, re, rs)
                assert h is not None and not len(left)
            wc[0] += k_windows
            return k_windows * w / (time.perf_counter() - t0)

        def run_pipelined(k_windows, depth):
            staging = [dict() for _ in range(depth + 2)]
            inflight = deque()
            i = 0
            seq = 0
            t0 = time.perf_counter()
            while i < k_windows or inflight:
                while i < k_windows and len(inflight) < depth:
                    g = min(scan, k_windows - i)
                    wins = [variants[(wc[0] + i + d) % nv]
                            for d in range(g)]
                    h = eng.launch_columnar_windows(
                        wins, 0, now_ms=now + wc[0] + i,
                        staging=staging[seq % len(staging)])
                    assert h is not None and len(h[0]) == g \
                        and h[1] is None
                    inflight.append((h, g, seq % len(outs_pool)))
                    i += g
                    seq += 1
                h, g, oslot = inflight.popleft()
                lefts = eng.collect_columnar_windows(
                    h, outs_pool[oslot][:g])
                assert all(not len(l) for l in lefts)
            wc[0] += k_windows
            return k_windows * w / (time.perf_counter() - t0)

        return run_lockstep, run_pipelined

    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731

    # ---- headline: frame-width windows, depth probe {1, 3, 6} ----------
    fw_vars = make_variants(FRAME_WIDTH, 8)
    run_lockstep, run_pipelined = make_runners(FRAME_WIDTH, fw_vars)
    for _ in range(2):  # warm: compiles + page-faults the touched rows
        run_lockstep(24)
        run_pipelined(24, 3)
    lockstep = []
    probe = {d: [] for d in (1, 3, 6)}
    for _ in range(3):  # alternate so neither path rides warmer pages
        lockstep.append(run_lockstep(n_windows))
        for d in probe:
            probe[d].append(run_pipelined(n_windows, d))
    lockstep_med = med(lockstep)
    probe_med = {d: round(med(rs_), 1) for d, rs_ in probe.items()}
    best_depth = max(probe_med, key=probe_med.get)

    # ---- secondary: max-width windows (kernel-bound on a CPU rig) ------
    mw = eng.max_width
    mw_vars = make_variants(mw, 4)
    run_lockstep_mw, run_pipelined_mw = make_runners(mw, mw_vars)
    for _ in range(2):
        run_lockstep_mw(8)
        run_pipelined_mw(8, 3)
    mw_lock = med([run_lockstep_mw(24) for _ in range(3)])
    mw_pipe = med([run_pipelined_mw(24, 3) for _ in range(3)])

    return {
        "columnar_pipeline_decisions_per_sec": probe_med[best_depth],
        "columnar_pipeline": {
            "scope": "zero-object columnar wire path (peerlink layout "
                     "cols -> launch_columnar_windows -> response "
                     f"columns), {FRAME_WIDTH}-wide frame windows "
                     f"(MAX_FRAME_ITEMS), scan groups <= {scan} windows/"
                     "launch, keydir(10M resident)",
            "lockstep_decisions_per_sec": round(lockstep_med, 1),
            "depth_probe_decisions_per_sec":
                {str(d): r for d, r in probe_med.items()},
            "depth": best_depth,
            "speedup_vs_lockstep": round(
                probe_med[best_depth] / max(lockstep_med, 1.0), 2),
            "windows_per_run": n_windows,
            "max_width_row": {
                "width": mw,
                "lockstep_decisions_per_sec": round(mw_lock, 1),
                "pipelined_d3_decisions_per_sec": round(mw_pipe, 1),
                "speedup_vs_lockstep": round(mw_pipe / max(mw_lock, 1.0),
                                             2),
                "note": "kernel-bound at this width on a shared-core CPU "
                        "rig; the overlap margin is the link-bound rig's "
                        "lever (BENCH_r05)",
            },
        },
    }


def _multichip_section() -> dict:
    """Fold the latest MULTICHIP_r*.json into the bench record.

    The multichip runs land as sibling artifacts of the BENCH_r* files;
    surfacing the newest one here makes every bench record self-contained
    about the mesh tier's last known state instead of requiring a second
    artifact lookup."""
    import glob
    import os

    files = sorted(glob.glob(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "MULTICHIP_r*.json")))
    if not files:
        return {}
    latest = files[-1]
    try:
        with open(latest) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        return {"multichip": {"source": os.path.basename(latest),
                              "error": str(e)}}
    out = {"source": os.path.basename(latest)}
    for k in ("n_devices", "rc", "ok", "skipped", "note"):
        if k in data:
            out[k] = data[k]
    return {"multichip": out}


def _skew_bench(n_calls: int = 1200, n_keys: int = 32,
                zipf_a: float = 1.1) -> dict:
    """Zipf-head skew through a REAL 2-node loopback cluster: the hot-key
    lease tier's acceptance row (BENCH_r09).

    Three workloads through the same client node, measured at the client
    (per-call p50/p99) and at the hot key's owner (engine-request share —
    the work consistent hashing concentrates on one host):

    - uniform: n_keys keys, flat — the no-skew reference row;
    - zipf_off: Zipf-`zipf_a` keys, leases disabled — every head hit is a
      forward RPC to the owner;
    - zipf_on: the SAME key sequence with GUBER_HOT_LEASES semantics armed
      — the owner detects the head, grants budgeted leases, and the client
      node answers the head locally, draining hits asynchronously.

    The claim under test: zipf_on cuts both the client p99 and the owner's
    work share vs zipf_off, approaching the uniform row."""
    from gubernator_tpu.cluster.harness import LocalCluster
    from gubernator_tpu.types import RateLimitReq

    rng = np.random.RandomState(9)
    zipf_seq = [int(z) % n_keys for z in rng.zipf(zipf_a, size=n_calls)]
    uniform_seq = [int(u) for u in rng.randint(0, n_keys, size=n_calls)]

    def reqs_for(seq, prefix):
        # leading digits vary: trailing-suffix keys can collapse onto one
        # fnv ring arc (cluster/harness.py ownership probes do the same)
        return [RateLimitReq(name="skew", unique_key=f"{k}{prefix}",
                             hits=1, limit=1 << 30, duration=3_600_000)
                for k in seq]

    head = int(np.bincount(zipf_seq).argmax())

    # The 2-node fnv ring can land arbitrarily lopsided for one boot's
    # random ports (one arc owning ~everything) — a row where the client
    # owns nothing measures only the micro-batch window, not skew. Re-roll
    # until both nodes own a real share of the workload's keys.
    c = None
    for _ in range(6):
        c = LocalCluster().start(2)
        owners = [c.owner_of(f"skew_{k}z").address for k in range(n_keys)]
        share = owners.count(owners[0]) / n_keys
        if 0.2 <= share <= 0.8:
            break
        c.stop()
    try:
        hot_owner = c.owner_of(f"skew_{head}z")
        # drive from the node that does NOT own the Zipf head, so head
        # hits actually cross the wire (the skew problem under test)
        client = next(ci for ci in c.instances if ci is not hot_owner)

        leased_before = [0]

        def run_row(reqs, head_unique):
            # per-engine request deltas attribute the row's work
            before = [ci.instance.backend.stats.requests
                      for ci in c.instances]
            lat = np.empty(len(reqs))
            head_mask = np.zeros(len(reqs), bool)
            t_start = time.perf_counter()
            for i, r in enumerate(reqs):
                head_mask[i] = r.unique_key == head_unique
                t0 = time.perf_counter()
                resp = client.instance.get_rate_limits([r])[0]
                lat[i] = time.perf_counter() - t0
                if resp.error:
                    raise RuntimeError(resp.error)
            wall = time.perf_counter() - t_start
            owner_i = c.instances.index(hot_owner)
            deltas = [ci.instance.backend.stats.requests - b
                      for ci, b in zip(c.instances, before)]
            leased = client.instance.leases.stats["local_answers"] \
                - leased_before[0]
            leased_before[0] += leased
            head_lat = lat[head_mask]
            row = {
                "calls": len(reqs),
                "calls_per_sec": round(len(reqs) / wall, 1),
                "client_p50_ms": round(
                    float(np.percentile(lat, 50) * 1e3), 3),
                "client_p99_ms": round(
                    float(np.percentile(lat, 99) * 1e3), 3),
                "hot_owner_engine_requests": int(deltas[owner_i]),
                "hot_owner_work_share": round(
                    deltas[owner_i] / max(sum(deltas), 1), 3),
                "leased_answers_total": int(leased),
            }
            if head_lat.size:
                # the skew victim's own latency: head-key calls are the
                # ones a lease converts from cross-host forwards (the
                # micro-batch window + RPC) into local table reads
                row["head_calls"] = int(head_lat.size)
                row["head_p50_ms"] = round(
                    float(np.percentile(head_lat, 50) * 1e3), 3)
                row["head_p99_ms"] = round(
                    float(np.percentile(head_lat, 99) * 1e3), 3)
            return row

        head_unique = f"{head}z"
        rows = {"uniform": run_row(reqs_for(uniform_seq, "u"), "")}
        rows["zipf_off"] = run_row(reqs_for(zipf_seq, "z"), head_unique)

        for ci in c.instances:
            b = ci.instance.conf.behaviors
            b.hot_leases = True
            # the head must cross the rate threshold at this rig's
            # closed-loop call rate (Zipf-1.1 head ≈ 11% of ~100-200/s)
            # while the ~2%-share tail keys stay cold
            b.hot_lease_rate = 5.0
            b.hot_lease_window_s = 0.5
            b.hot_lease_ttl_s = 1.0
            b.hot_lease_fraction = 0.5
            ci.instance.leases.arm()
        rows["zipf_on"] = run_row(reqs_for(zipf_seq, "z"), head_unique)
        rows["zipf_a"] = zipf_a
        rows["n_keys"] = n_keys
        return {"skew": rows}
    finally:
        c.stop()


class _LinkLagBackend:
    """Bench-only engine wrapper emulating a LINK-BOUND rig on the CPU
    fallback: a launched columnar group's readback lands `link_ms` after
    dispatch (the transfer progresses in the background while the host
    works, as on any link-bound attachment), so
    collect_columnar_windows blocks only for the REMAINDER. A serving
    loop that overlaps other work with in-flight readbacks pays nothing;
    one that drains right after launching pays the full latency."""

    def __init__(self, eng, link_ms: float):
        self._eng = eng
        self._lag = link_ms / 1e3
        self._due = {}

    def __getattr__(self, name):
        return getattr(self._eng, name)

    def launch_columnar_windows(self, *a, **kw):
        h = self._eng.launch_columnar_windows(*a, **kw)
        if h is not None:
            self._due[id(h)] = time.perf_counter() + self._lag
        return h

    def collect_columnar_windows(self, h, outs):
        wait = self._due.pop(id(h), 0) - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        return self._eng.collect_columnar_windows(h, outs)


def _wire_bench(n_frames: int = 48, frame_w: int = 1024,
                inflight: int = 8, link_ms: float = 8.0) -> dict:
    """Wire contract v1 vs v2 over a real loopback peerlink (BENCH_r10).

    The client keeps `inflight` frames of `frame_w` requests in flight
    (call_async closed loop, replenish-on-complete); the only variable
    is the wire contract: v1 whole-frame replies with _worker_v1's
    per-pull barrier (the PR-7 baseline) vs v2 seq-numbered partial
    posts with cross-pull pipelining (_worker_v2). One worker, so the
    contract itself — not worker-count parallelism — is what's measured;
    frame_w spans four max_width=256 sub-windows so every pull carries
    multiple scan groups.

    Two regimes per contract: the bare CPU-fallback rig (zero-latency
    loopback — the barrier has nothing to hide, so v1 and v2 should tie
    within the partial-post overhead), and a LINK-EMULATED rig
    (readbacks land `link_ms` after dispatch) — the link-bound regime where the v1 contract drains the
    pipeline at every pull boundary while v2 keeps it fed. The rows
    record the negotiated version and the server's boundary-stall and
    partial-post counters, so the win is attributable to removed
    stalls, not noise."""
    import collections

    from gubernator_tpu.models.engine import Engine
    from gubernator_tpu.service.config import InstanceConfig
    from gubernator_tpu.service.instance import Instance
    from gubernator_tpu.service.peerlink import (
        METHOD_GET_PEER_RATE_LIMITS,
        PeerLinkClient,
        PeerLinkService,
    )
    from gubernator_tpu.types import RateLimitReq

    med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731

    def run(v2: bool, lag_ms: float) -> dict:
        eng = Engine(capacity=1 << 17, min_width=8, max_width=256)
        if not eng.supports_columnar():
            raise RuntimeError("native columnar prep unavailable")
        back = _LinkLagBackend(eng, lag_ms) if lag_ms else eng
        inst = Instance(InstanceConfig(backend=back),
                        advertise_address="self")
        svc = PeerLinkService(inst, port=0, workers=1, pipeline_depth=3,
                              pipeline_scan=2, wire_v2=v2)
        cli = PeerLinkClient(f"127.0.0.1:{svc.port}", wire_v2=v2)
        try:
            def frame(i):
                base = (i * frame_w) % (1 << 16)
                return [RateLimitReq(
                    name="w", unique_key=f"k{base + j}", hits=1,
                    limit=1 << 30, duration=3_600_000)
                    for j in range(frame_w)]

            def drive(k):
                pend = collections.deque()
                i = 0
                t0 = time.perf_counter()
                while i < k or pend:
                    while i < k and len(pend) < inflight:
                        fut, _ = cli.call_async(
                            METHOD_GET_PEER_RATE_LIMITS, frame(i))
                        pend.append(fut)
                        i += 1
                    resps = pend.popleft().result(timeout=120)
                    assert len(resps) == frame_w
                return k * frame_w / (time.perf_counter() - t0)

            drive(16)  # warm: compiles + server buffer ring
            rate = med([drive(n_frames) for _ in range(3)])
            return {
                "decisions_per_sec": round(rate, 1),
                "negotiated_version": cli.wire_version,
                "partial_posts": svc.wire_partial_posts(),
                "pull_boundary_stalls": svc.stats["pull_boundary_stalls"],
            }
        finally:
            cli.close()
            svc.close()
            inst.close()

    def pair(lag_ms: float) -> dict:
        v1 = run(False, lag_ms)
        v2 = run(True, lag_ms)
        return {
            "v1": v1,
            "v2": v2,
            "speedup_v2_vs_v1": round(
                v2["decisions_per_sec"]
                / max(v1["decisions_per_sec"], 1.0), 2),
        }

    cpu_rig = pair(0.0)
    emulated = pair(link_ms)
    return {
        "wire_v2_speedup_link_bound": emulated["speedup_v2_vs_v1"],
        "wire": {
            "scope": "loopback peerlink, closed loop with "
                     f"{inflight} x {frame_w}-request frames in flight, "
                     "1 worker, pipelined columnar server (depth 3, "
                     "scan 2, max_width 256); v1 = whole-frame + "
                     "per-pull barrier, v2 = partial posts + cross-pull "
                     "pipelining (docs/wire.md)",
            "cpu_rig": cpu_rig,
            "link_emulated": {
                **emulated,
                "link_ms": link_ms,
                "note": "readbacks land link_ms after dispatch "
                        "(a slow link emulated on "
                        "the CPU fallback; transfers progress while "
                        "the host works) — the link-bound regime where "
                        "the per-pull barrier is the structural cost",
            },
            "frames_per_run": n_frames,
            "frame_width": frame_w,
            "inflight_frames": inflight,
        },
    }


def _reshard_bench(n_resident: int = 1_000_000,
                   fg_keys: int = 120) -> dict:
    """Live resharding at scale: handoff duration + serving-path impact
    with 1M resident counter rows on the departing owner (BENCH_r13).

    A real 2-node loopback cluster, reshard armed. The donor node is
    staged with `n_resident` donor-owned rows through the engine's
    snapshot-slab inject path (the same path transfer frames use), then
    `evacuate()` streams every row to the survivor over the debug RPC —
    plan, chunk-cut, stream, commit, measured wall-clock end to end.
    A foreground client meanwhile drives survivor-owned keys through
    the survivor (the importer: its serving path carries the intercept
    checks AND the frame injections), sampled per-call before and
    during the handoff — the serving-impact row.

    The claims under test: handoff duration scales with rows at
    wire+inject cost (no quadratic planning), and the importer's
    foreground p99 stays in the same regime while 1M rows stream in."""
    import dataclasses
    import threading

    from gubernator_tpu.cluster.harness import LocalCluster, test_behaviors
    from gubernator_tpu.types import RateLimitReq

    beh = dataclasses.replace(test_behaviors(), reshard=True,
                              reshard_ttl_s=10.0, reshard_grace_s=0.5)
    # table capacity: donor residents + foreground keys + slack, on
    # BOTH nodes (the survivor absorbs the whole donor set)
    c = LocalCluster().start(2, capacity=1 << 21, behaviors=beh)
    try:
        time.sleep(0.7)  # boot grace
        survivor, donor = c.instances[0], c.instances[1]

        # ---- stage: n_resident donor-OWNED rows via the slab inject
        # path. Ownership is the single-point ring's call, so candidate
        # keys are partitioned by the live picker and the donor takes
        # the majority side (re-rolling ports for a balanced ring at 1M
        # keys costs more than over-generating candidates).
        get_peer = survivor.instance.get_peer
        probe = [f"reshard_rk{i:07d}" for i in range(50_000)]
        donor_share = sum(get_peer(k).info.address == donor.address
                          for k in probe) / len(probe)
        if donor_share < 0.5:
            survivor, donor = donor, survivor
            donor_share = 1.0 - donor_share
        donor_keys: list = []
        i = 0
        cap = max(4 * n_resident, 200_000)
        while len(donor_keys) < n_resident and i < cap:
            k = f"reshard_rk{i:07d}"
            if get_peer(k).info.address == donor.address:
                donor_keys.append(k)
            i += 1
        now_ms = int(time.time() * 1000)
        chunk = 8192
        t0 = time.perf_counter()

        def slabs():
            for lo in range(0, len(donor_keys), chunk):
                ks = [k.encode() for k in donor_keys[lo:lo + chunk]]
                m = len(ks)
                off = np.zeros(m + 1, np.int64)
                np.cumsum([len(b) for b in ks], out=off[1:])
                rows = np.zeros((m, 7), np.int64)
                rows[:, 0] = 0  # TOKEN_BUCKET
                rows[:, 1] = 1 << 20  # limit
                rows[:, 2] = np.arange(lo, lo + m) % (1 << 20)  # remaining
                rows[:, 3] = 3_600_000  # duration
                rows[:, 4] = now_ms
                rows[:, 5] = now_ms + 3_600_000  # expire_at
                yield b"".join(ks), off, rows

        donor.instance.backend.load_snapshot_slabs(slabs())
        stage_s = time.perf_counter() - t0

        # ---- foreground load on the IMPORTER, sampled per call.
        # Leading digits vary: trailing-suffix keys can collapse onto
        # one fnv ring arc (the _skew_bench ownership-probe caveat), and
        # a draw where every foreground key lands on the DONOR measures
        # nothing — over-generate and keep the survivor-owned ones.
        fg = [r for r in
              (RateLimitReq(name="rfg", unique_key=f"{j:04d}fg", hits=1,
                            limit=1 << 30, duration=3_600_000)
               for j in range(20 * fg_keys))
              if get_peer(r.hash_key()).info.address == survivor.address
              ][:fg_keys]
        lat, marks, fg_errors = [], [], []
        stop = threading.Event()

        def drive():
            while not stop.is_set():
                for r in fg:
                    t1 = time.perf_counter()
                    try:
                        resp = survivor.instance.get_rate_limits([r])[0]
                    except Exception as e:  # noqa: BLE001
                        fg_errors.append(repr(e))
                        continue
                    lat.append(time.perf_counter() - t1)
                    if resp.error:
                        fg_errors.append(resp.error)
                time.sleep(0.005)

        th = threading.Thread(target=drive, daemon=True)
        th.start()
        time.sleep(1.5)  # quiet-window baseline
        marks.append(len(lat))

        # ---- the handoff: evacuate() returns once every export commits
        t0 = time.perf_counter()
        drained = donor.instance.reshard.evacuate(timeout_s=300)
        handoff_s = time.perf_counter() - t0
        marks.append(len(lat))
        time.sleep(1.0)  # post-handoff window
        stop.set()
        th.join(timeout=10)

        stats = donor.instance.reshard.debug()["stats"]
        quiet = np.asarray(lat[:marks[0]])
        during = np.asarray(lat[marks[0]:marks[1]])
        after = np.asarray(lat[marks[1]:])

        def pcts(a):
            if not a.size:
                return {}
            return {"calls": int(a.size),
                    "p50_ms": round(float(np.percentile(a, 50) * 1e3), 3),
                    "p99_ms": round(float(np.percentile(a, 99) * 1e3), 3)}

        return {"reshard": {
            "scope": "2-node loopback cluster, evacuate() streaming the "
                     "donor's whole resident set to the survivor over "
                     "the debug RPC (plan + chunk-cut + stream + "
                     "commit), foreground client on the importer",
            "resident_rows": len(donor_keys),
            "donor_ring_share": round(donor_share, 3),
            "stage_seconds": round(stage_s, 2),
            "drained": bool(drained),
            "handoff_seconds": round(handoff_s, 2),
            "rows_moved": int(stats["rows_out"]),
            "rows_per_sec": round(stats["rows_out"] / max(handoff_s, 1e-6), 1),
            "transfer_MBps": round(
                stats["bytes_out"] / max(handoff_s, 1e-6) / 1e6, 2),
            "export_commits": int(stats["export_commits"]),
            "export_aborts": int(stats["export_aborts"]),
            "chunk_rows": beh.reshard_chunk_rows,
            "importer_foreground": {
                "keys": len(fg),
                "errors": len(fg_errors),
                "quiet": pcts(quiet),
                "during_handoff": pcts(during),
                "after": pcts(after),
            },
        }}
    finally:
        c.stop()


def _ledger_bench(n_calls: int = 1500, batch: int = 64, reps: int = 3) -> dict:
    """Decision-ledger overhead on the serving path: the SAME single-node
    Instance serving identical batch streams with the ledger attributing
    every window vs the GUBER_LEDGER=0 hatch (which turns every engine
    hook into one attribute test — every hook site reads `led.enabled`
    live, so the flag flips on a running instance the way the profiler
    hatch does). The flag alternates every CHUNK calls within one pass,
    same drift-regime rationale as _obs_bench; acceptance is
    overhead <= 2%.

    The hot-path cost under test is the pending-ring parking: one numpy
    column copy + ring append per engine window group (the audit itself
    rides the harvest cadence, off the serving path). The per-audit
    drain/fold/roll cost is timed directly and duty-cycled at the 60 s
    harvest cadence (amortized_overhead_pct, informational)."""
    from gubernator_tpu.models.engine import Engine
    from gubernator_tpu.service.config import InstanceConfig
    from gubernator_tpu.service.instance import Instance
    from gubernator_tpu.types import PeerInfo, RateLimitReq

    AUDIT_PROD_S = 60.0
    inst = Instance(InstanceConfig(backend=Engine(capacity=262_144),
                                   ledger_enabled=True),
                    advertise_address="127.0.0.1:1")
    inst.set_peers([PeerInfo(address="127.0.0.1:1")])  # self-owned: no RPC
    led = inst.ledger
    frames = [
        [RateLimitReq(name="ledbench", unique_key=f"k{(i * batch + j) % 4096}",
                      hits=1, limit=1 << 30, duration=3_600_000)
         for j in range(batch)]
        for i in range(n_calls)
    ]
    try:
        for f in frames[:100]:  # compile + warm the width bucket
            inst.get_rate_limits(f)

        import gc
        import statistics

        CHUNK = 25
        elapsed = {True: 0.0, False: 0.0}
        calls = {True: 0, False: 0}
        pair_overheads = []  # median over ABBA chunk quads
        gc_was_enabled = gc.isenabled()
        gc.disable()
        try:
            for rep in range(reps):
                i = 0
                while i + 4 * CHUNK <= n_calls:
                    # ABBA within one quad: the second chunk of a pair
                    # always rides warmer state than the first, so a
                    # plain AB pairing measures the order effect (~1% on
                    # this rig — larger than the cost under test). The
                    # mirrored half cancels it and linear drift exactly.
                    rate = {True: [], False: []}
                    for enabled in (True, False, False, True):
                        led.enabled = enabled
                        chunk = frames[i:i + CHUNK]
                        i += CHUNK
                        t0 = time.perf_counter()
                        for f in chunk:
                            inst.get_rate_limits(f)
                        dt = time.perf_counter() - t0
                        elapsed[enabled] += dt
                        calls[enabled] += CHUNK
                        rate[enabled].append(CHUNK * batch / dt)
                    r_on = sum(rate[True]) / 2
                    r_off = sum(rate[False]) / 2
                    pair_overheads.append((r_off - r_on) / r_off)
                # drain the parked windows between reps so the pending
                # ring never saturates mid-measurement (the audit is
                # off-path; running it inside the quad loop perturbs the
                # cache right before a timed chunk)
                led.enabled = True
                led.audit(inst.backend, force=True)
        finally:
            if gc_was_enabled:
                gc.enable()
        led.enabled = True
        on = calls[True] * batch / elapsed[True]
        off = calls[False] * batch / elapsed[False]
        overhead_pct = statistics.median(pair_overheads) * 100.0

        # per-audit cost, timed directly and duty-cycled at the 60 s
        # harvest cadence (informational — the audit is off-path)
        audit_costs = []
        for _ in range(20):
            for f in frames[:10]:  # park fresh windows to drain
                inst.get_rate_limits(f)
            t0 = time.perf_counter()
            led.audit(inst.backend, force=True)
            audit_costs.append(time.perf_counter() - t0)
        audit_ms = statistics.median(audit_costs) * 1e3
        amortized_pct = 100.0 * audit_ms * 1e-3 / AUDIT_PROD_S

        lt = led.totals()
        return {
            "ledger": {
                "ledger_on_decisions_per_sec": round(on, 1),
                "ledger_off_decisions_per_sec": round(off, 1),
                # positive = the armed ledger costs throughput; median
                # over on/off chunk pairs, hiccup-robust. budget <= 2%
                "overhead_pct": round(overhead_pct, 2),
                # per-audit drain/fold cost duty-cycled at the 60 s
                # harvest cadence — off the serving path
                "amortized_audit_overhead_pct": round(amortized_pct, 4),
                "audit_ms": round(audit_ms, 3),
                "attempted_hits": lt["attempted"],
                "windows_rolled": lt["windows_rolled"],
                "violations": lt["violations"],
                "keys_tracked": lt["keys_tracked"],
                "pending_dropped": lt["pending_dropped"],
                "chunk_quads": len(pair_overheads),
                "reps": reps,
                "batch": batch,
                "calls_per_rep": n_calls,
            }
        }
    finally:
        inst.close()


def _witness_bench(n_calls: int = 1200, batch: int = 64, reps: int = 3) -> dict:
    """Lock-witness overhead on the serving path: two otherwise identical
    single-node Instances, one constructed under GUBER_LOCK_WITNESS=1
    (every canonical lock an order-checked wrapper validating against
    the committed lockmap) and one under the production default (bare
    threading primitives), serving identical batch streams. The flag
    alternates every CHUNK calls within one pass — same drift-regime
    rationale as _obs_bench — but by alternating INSTANCES: the witness
    wraps locks at construction time, so it cannot flip on a live
    object the way the profiler hatch can. Tier-1 pays this cost on
    every suite run; production pays zero (the off path is the
    differential-tested bit-identical hatch, tests/test_witness.py).
    Budget <= 30% (measured ~26%, r16): every canonical-lock
    acquisition pays ~2.3 us of pure-Python bookkeeping (held-list
    fetch, order scan against the committed lockmap, single-frame site
    stamp), and the serving path takes several locks per decision
    batch (engine, combiner windows, profiler phase hists). Report-side
    stack walks are lazy — only an inversion or a first-sighting
    unknown edge pays them — so the floor is interpreter call overhead,
    not capture; shaving it further would mean duplicating the
    bookkeeping inline in the wrapper, a correctness hazard in the
    instrument meant to catch correctness bugs. The cost is a tier-1
    tax only: production runs the bare primitives.

    A directly-timed bare acquire/release pair for each lock flavor
    rides along informationally."""
    import gc
    import os
    import statistics

    from gubernator_tpu.models.engine import Engine
    from gubernator_tpu.obs import witness
    from gubernator_tpu.service.config import InstanceConfig
    from gubernator_tpu.service.instance import Instance
    from gubernator_tpu.types import PeerInfo, RateLimitReq

    def make_instance(enabled: bool) -> Instance:
        prev = os.environ.get("GUBER_LOCK_WITNESS")
        os.environ["GUBER_LOCK_WITNESS"] = "1" if enabled else "0"
        try:
            inst = Instance(InstanceConfig(backend=Engine(capacity=65_536)),
                            advertise_address="127.0.0.1:1")
        finally:
            if prev is None:
                os.environ.pop("GUBER_LOCK_WITNESS", None)
            else:
                os.environ["GUBER_LOCK_WITNESS"] = prev
        inst.set_peers([PeerInfo(address="127.0.0.1:1")])  # self-owned
        return inst

    insts = {True: make_instance(True), False: make_instance(False)}
    frames = [
        [RateLimitReq(name="witbench", unique_key=f"k{(i * batch + j) % 4096}",
                      hits=1, limit=1 << 30, duration=3_600_000)
         for j in range(batch)]
        for i in range(n_calls)
    ]
    try:
        for f in frames[:100]:  # compile + warm both width buckets
            insts[True].get_rate_limits(f)
            insts[False].get_rate_limits(f)

        CHUNK = 25
        elapsed = {True: 0.0, False: 0.0}
        calls = {True: 0, False: 0}
        pair_overheads = []
        gc_was_enabled = gc.isenabled()
        gc.disable()
        try:
            for rep in range(reps):
                i = 0
                while i + 2 * CHUNK <= n_calls:
                    first = len(pair_overheads) % 2 == 0
                    rate = {}
                    for enabled in (first, not first):
                        chunk = frames[i:i + CHUNK]
                        i += CHUNK
                        inst = insts[enabled]
                        t0 = time.perf_counter()
                        for f in chunk:
                            inst.get_rate_limits(f)
                        dt = time.perf_counter() - t0
                        elapsed[enabled] += dt
                        calls[enabled] += CHUNK
                        rate[enabled] = CHUNK * batch / dt
                    pair_overheads.append(
                        (rate[False] - rate[True]) / rate[False])
        finally:
            if gc_was_enabled:
                gc.enable()
        on = calls[True] * batch / elapsed[True]
        off = calls[False] * batch / elapsed[False]
        overhead_pct = statistics.median(pair_overheads) * 100.0

        # bare acquire/release cost per flavor (informational): the
        # serving call amortizes a handful of acquisitions over a whole
        # batch. Explicit acquire()/release() rather than `with` — a
        # loop-variable context manager would be an unresolved scope to
        # the static lockmap (tests pin those to zero); the runtime
        # witness still checks every one of these acquisitions.
        N_ACQ = 20_000
        acq_ns = {}
        for label, lock in (("on", insts[True].backend._lock),
                            ("off", insts[False].backend._lock)):
            t0 = time.perf_counter()
            for _ in range(N_ACQ):
                lock.acquire()
                lock.release()
            acq_ns[label] = (time.perf_counter() - t0) / N_ACQ * 1e9

        snap = witness.the_witness().snapshot()
        return {
            "lock_witness": {
                "witness_on_decisions_per_sec": round(on, 1),
                "witness_off_decisions_per_sec": round(off, 1),
                # positive = the armed witness costs throughput; median
                # over on/off chunk pairs, hiccup-robust. budget <= 30%
                "overhead_pct": round(overhead_pct, 2),
                "acquire_release_ns_on": round(acq_ns["on"], 1),
                "acquire_release_ns_off": round(acq_ns["off"], 1),
                "observed_edges": len(snap["observed"]),
                "uncommitted_edges": len(snap["unknown"]),
                "inversions": len(snap["inversions"]),
                "chunk_pairs": len(pair_overheads),
                "reps": reps,
                "batch": batch,
                "calls_per_rep": n_calls,
            }
        }
    finally:
        insts[True].close()
        insts[False].close()


def main() -> None:
    watchdog = _init_watchdog()
    import jax
    import jax.numpy as jnp

    jax.devices()  # backend init is the one step the watchdog bounds;
    watchdog.cancel()  # compiles/timing may run long safely

    from gubernator_tpu.ops.decide import (
        compact_window,
        decide_packed,
        decide_scan_packed,
        decide_scan_packed_compact,
        make_table,
    )
    from gubernator_tpu.utils.platform import donation_supported

    def make_windows(seed: int, k: int) -> np.ndarray:
        r = np.random.RandomState(seed)
        p = np.zeros((k, 9, BATCH_WIDTH), np.int64)
        for i in range(k):
            # distinct slots per window (engine guarantees via rounds)
            p[i, 0] = r.choice(TABLE_CAPACITY, BATCH_WIDTH, replace=False)
            p[i, 1] = r.randint(0, 5, BATCH_WIDTH)
            p[i, 2] = r.choice([100, 1000, 10000], BATCH_WIDTH)
            p[i, 3] = 60_000
            p[i, 4] = r.randint(0, 2, BATCH_WIDTH)
        return p

    donate = donation_supported()
    dargs = dict(donate_argnums=(0,)) if donate else {}
    scan_step = jax.jit(decide_scan_packed, **dargs)
    one_step = jax.jit(decide_packed, **dargs)

    def force(resp) -> int:
        """Completion barrier: a data-dependent scalar fetch.

        Fetching one element of the result waits for the whole
        dependency chain on any backend, so a throughput figure can never
        be an enqueue rate."""
        return int(np.asarray(resp[(0,) * resp.ndim]))

    # Device-resident inputs: measure the kernel tier, not host staging.
    scans = [jnp.asarray(make_windows(s, SCAN_K)) for s in range(N_VARIANTS)]
    singles = [jnp.asarray(make_windows(100 + s, 1)[0]) for s in range(N_VARIANTS)]

    now = 1_700_000_000_000
    state = make_table(TABLE_CAPACITY)

    # ---- warm-up / calibrate ------------------------------------------------
    state, resp = scan_step(state, scans[0], now)
    force(resp)
    t0 = time.perf_counter()
    state, resp = scan_step(state, scans[1], now + 1)
    force(resp)
    per_call = max(time.perf_counter() - t0, 1e-6)
    iters = max(5, min(3000, int(TARGET_SECONDS / per_call)))

    # ---- headline: scan-coalesced throughput, completion-forced -------------
    t_start = time.perf_counter()
    for i in range(iters):
        state, resp = scan_step(state, scans[i % N_VARIANTS], now + 2 + i)
    t_enqueue = time.perf_counter() - t_start  # dispatch-only (diagnostic)
    force(resp)  # wait for the WHOLE chain to really finish
    elapsed = time.perf_counter() - t_start
    decisions_per_sec = iters * SCAN_K * BATCH_WIDTH / elapsed
    enqueue_rate = iters * SCAN_K * BATCH_WIDTH / max(t_enqueue, 1e-9)

    # ---- extra: one-window-per-dispatch, completion-forced ------------------
    state, resp = one_step(state, singles[0], now)
    force(resp)
    t0 = time.perf_counter()
    state, resp = one_step(state, singles[1], now + 1)
    force(resp)
    sd_per_call = max(time.perf_counter() - t0, 1e-6)
    sd_iters = max(5, min(5000, int(TARGET_SECONDS / sd_per_call)))
    t0 = time.perf_counter()
    for i in range(sd_iters):
        state, resp = one_step(state, singles[i % N_VARIANTS], now + i)
    force(resp)
    single_dispatch = sd_iters * BATCH_WIDTH / (time.perf_counter() - t0)

    # ---- extra: synchronous per-window latency (incl. readback) -------------
    lat_iters = max(5, min(sd_iters, 50))
    lat = np.zeros(lat_iters)
    for i in range(lat_iters):
        t1 = time.perf_counter()
        state, resp = one_step(state, singles[i % N_VARIANTS], now + i)
        force(resp)
        lat[i] = time.perf_counter() - t1

    # ---- extra: compact (i32) staging variant — the wire format for
    # ingest-bound links (20 B/decision up instead of 72; see
    # ops/decide.py "compact") -----------------------------------------------
    compact_step = jax.jit(decide_scan_packed_compact, **dargs)
    compact_np = [compact_window(np.asarray(s)) for s in scans]
    assert all(c is not None for c in compact_np), \
        "bench windows must stay compact-eligible (no gregorian, values < 2^31)"
    compacts = [jnp.asarray(c) for c in compact_np]
    state, resp = compact_step(state, compacts[0], now)
    force(resp)
    t0 = time.perf_counter()
    c_iters = max(3, iters // 2)
    for i in range(c_iters):
        state, resp = compact_step(state, compacts[i % N_VARIANTS], now + i)
    force(resp)
    compact_rate = c_iters * SCAN_K * BATCH_WIDTH / (time.perf_counter() - t0)

    # ---- extra: FULL serving path — key directory + columnar prep +
    # staging + kernel + demux (VERDICT r2 item 1). Real key strings
    # resolve through the 10M-entry C++ LRU directory and the GIL-free
    # columnar prep into a K-deep staging stack shipped in the LEAN wire
    # format (native/keydir.cpp keydir_prep_pack_lean): ONE i32 word per
    # decision — 4 B up, 8 B back = 12 B/decision round trip (the r5 wire
    # lever, DESIGN.md "Next wire lever"; interned was 16, compact 36,
    # wide 104). One transfer up, ONE scan dispatch, ONE fetch back; the
    # demux scatters each window's response rows to its items. On local
    # hardware the same path runs per-window with µs readbacks. ---------------
    from gubernator_tpu import native
    from gubernator_tpu.models.engine import Engine
    from gubernator_tpu.ops.decide import decide_scan_packed_lean

    # min_width 64 (not BATCH_WIDTH) so the columnar-pipeline section's
    # frame-width windows bucket at their own width instead of padding to
    # 8192; every other section drives exact-max-width windows and is
    # unaffected (bucket_width(8192) == 8192 either way)
    eng = Engine(capacity=TABLE_CAPACITY, min_width=64,
                 max_width=BATCH_WIDTH)
    serving_row = {}
    if eng.supports_columnar():
        rng = np.random.RandomState(7)
        CH = 100_000
        for s in range(0, TABLE_CAPACITY, CH):  # resident directory: 10M keys
            eng.directory.lookup([f"b_k{i}" for i in range(s, s + CH)])
        variants = []
        for _ in range(N_VARIANTS):
            ids = rng.choice(TABLE_CAPACITY, BATCH_WIDTH, replace=False)
            ukeys = [b"k%d" % i for i in ids]
            keys = b"".join(b"b" + u for u in ukeys)
            off = np.zeros(BATCH_WIDTH + 1, np.int32)
            np.cumsum([1 + len(u) for u in ukeys], out=off[1:])
            variants.append((
                keys, off, np.ones(BATCH_WIDTH, np.int32),
                np.ones(BATCH_WIDTH, np.int64),
                np.full(BATCH_WIDTH, 1 << 30, np.int64),
                np.full(BATCH_WIDTH, 3_600_000, np.int64),
                np.zeros(BATCH_WIDTH, np.int32),
                np.zeros(BATCH_WIDTH, np.int32)))
        K_SERVE = 128
        N_BUF = 8  # buffer ring; up to 6 cycles stay in flight (auto-tuned)
        lanes = [[None] * K_SERVE for _ in range(N_BUF)]
        iws = [np.empty((K_SERVE, BATCH_WIDTH), np.int32)
               for _ in range(N_BUF)]
        st = np.zeros(BATCH_WIDTH, np.int32)
        li = np.zeros(BATCH_WIDTH, np.int64)
        re = np.zeros(BATCH_WIDTH, np.int64)
        rs = np.zeros(BATCH_WIDTH, np.int64)

        # The serving cycle ships the LEAN wire format — i32[K, B] lane
        # words + one i64[128, 4] config table (4 KB, re-shipped only on
        # config churn) = 4 B/decision up; responses fetch as i32[K, 2, B]:
        # remaining | status<<31, and the reset delta = 8 B/decision back.
        # `limit` is an input echo the host already holds (config table).
        # (On local hardware the per-window engine path fetches the plain
        # 4-row form in µs.)
        def _step2(state, iw, cfg, now_ms):
            state, out = decide_scan_packed_lean(state, iw, cfg, now_ms)
            packed2 = jnp.stack(
                [out[:, 2, :] | (out[:, 0, :] << 31), out[:, 3, :]],
                axis=1)
            return state, packed2

        step2 = jax.jit(_step2, **dargs)

        istate = native.LeanPrepState()

        def prep_cycle(buf, w):
            # the C lean prep: directory lookup + validation + round
            # split + LEAN staging emit (4 B/item written instead of the
            # 72 B wide rows) in one GIL-free pass per window
            iwk, lns = iws[buf], lanes[buf]
            for d in range(K_SERVE):
                v = variants[(w + d) % N_VARIANTS]
                n0, lane, left, _inj = native.prep_pack_lean(
                    eng.directory, BATCH_WIDTH, v[0], v[1], v[2], v[3],
                    v[4], v[5], v[6], v[7], 0, iwk[d], istate)
                assert n0 == BATCH_WIDTH and not len(left)
                lns[d] = lane
            return iwk

        # the live Profiler meters this offline loop too, so the emitted
        # serving_decomposition below is the SAME derivation the
        # /v1/debug/profile endpoint serves (obs/profile.py) — one source
        # of truth, pinned by tests/test_profile_plane.py
        from gubernator_tpu.obs.profile import Profiler, serving_decomposition
        prof = Profiler(enabled=True)

        def drain(out2, buf, w, limit_col):
            t0 = time.perf_counter_ns()
            packed = np.asarray(out2)  # the one readback fetch
            prof.observe("readback", time.perf_counter_ns() - t0)
            t0 = time.perf_counter_ns()
            for d in range(K_SERVE):  # demux scatter per window
                lane = lanes[buf][d]
                w0 = packed[d, 0]
                delta = packed[d, 1].astype(np.int64)
                st[lane] = w0 >> 31 & 1
                re[lane] = w0 & 0x7FFFFFFF
                rs[lane] = np.where(delta < 0, 0, (now + w) + delta)
                li[lane] = limit_col
            prof.observe("demux", time.perf_counter_ns() - t0)
            return packed

        limit_col = np.int64(1 << 30)

        def probe_link_MBps():
            """Measure the rig's host->device and device->host bandwidth
            with cycle-sized transfers (completion-forced), so the JSON
            can separate 'what the framework does' from 'what the link
            did that minute'. Best of 2 each way."""
            up_bytes = K_SERVE * BATCH_WIDTH * 4  # one lean upload
            down_bytes = K_SERVE * BATCH_WIDTH * 8  # one 2-row readback
            up = np.zeros(up_bytes // 4, np.int32)
            up_s, down_s = [], []
            for _ in range(2):
                t0 = time.perf_counter()
                d = jnp.asarray(up)
                force(d)
                up_s.append(time.perf_counter() - t0)
                big = jnp.zeros(down_bytes // 4, jnp.int32) + d[0]
                force(big)
                t0 = time.perf_counter()
                np.asarray(big)
                down_s.append(time.perf_counter() - t0)
            return (up_bytes / min(up_s) / 1e6,
                    down_bytes / min(down_s) / 1e6)

        def run(cycles, w0, depth=2, prep_s=None):
            """A dedicated drainer thread owns the blocking readbacks, so
            the link is driven continuously; the main thread preps and
            dispatches (the columnar C prep releases the GIL, so the two
            overlap even on one core). Measured r3: a single-threaded loop
            made the cycle time the SUM of prep + transfer — this platform
            only moves bytes while a host thread is blocked in a fetch.
            `depth` bounds the in-flight cycles (queue backpressure)."""
            import queue as _q
            import threading as _t

            nonlocal state
            # buffer-ring safety: prep writes iws/lanes[c % N_BUF] while
            # up to `depth` earlier cycles (+1 inside the drainer) still
            # read theirs
            assert depth <= N_BUF - 2, (depth, N_BUF)
            q = _q.Queue(maxsize=depth)
            drain_err = []

            def drainer():
                while True:
                    item = q.get()
                    if item is None:
                        q.task_done()
                        return
                    try:
                        o, b, ww = item
                        drain(o, b, ww, limit_col)
                    except BaseException as e:  # surface, don't hang main
                        drain_err.append(e)
                    q.task_done()

            th = _t.Thread(target=drainer, daemon=True)
            th.start()
            cfg_dev = jnp.asarray(istate.cfg)  # ships once, not per cycle
            n_cfg0 = istate.n_cfg
            w = w0
            for c in range(cycles):
                t0 = time.perf_counter()
                iw = prep_cycle(c % N_BUF, w)
                if istate.n_cfg != n_cfg0:  # new config pairs: re-ship 4 KB
                    cfg_dev = jnp.asarray(istate.cfg)
                    n_cfg0 = istate.n_cfg
                dt = time.perf_counter() - t0
                prof.observe("prep", int(dt * 1e9))
                if prep_s is not None:
                    prep_s.append(dt)
                t0 = time.perf_counter_ns()
                state, out2 = step2(state, jnp.asarray(iw), cfg_dev, now + w)
                prof.observe("dispatch", time.perf_counter_ns() - t0)
                t0 = time.perf_counter_ns()
                q.put((out2, c % N_BUF, w))
                prof.observe("queue_wait", time.perf_counter_ns() - t0)
                w += K_SERVE
            q.put(None)
            q.join()
            if drain_err:
                raise drain_err[0]

        run(2, 0)  # warm + compile
        # auto-tune cycles-in-flight (VERDICT r4 item 2): probe each depth
        # with a run long enough that (a) the queue actually FILLS (a
        # probe shorter than ~2x the depth never engages backpressure and
        # measures nothing) and (b) fill/tail amortize enough for a
        # RELATIVE comparison — deeper pipelines hide more link jitter
        # until queueing stops paying
        depth_probe = {}
        w_base = 2 * K_SERVE
        PROBE_CYCLES = 12
        for depth in (3, 6):
            t0 = time.perf_counter()
            run(PROBE_CYCLES, w_base, depth=depth)
            depth_probe[depth] = (time.perf_counter() - t0) / PROBE_CYCLES
            w_base += PROBE_CYCLES * K_SERVE
        depth = min(depth_probe, key=depth_probe.get)
        per_cycle = max(depth_probe[depth], 1e-6)
        # enough cycles that pipeline fill + the serial drain tail (~1.5
        # cycles of link time) amortize below ~10% of the measurement —
        # 3-4 cycles UNDERSTATES the steady-state serving rate badly.
        # The headline is the MEDIAN of NINE independent completion-forced
        # segments (each long enough to amortize fill/tail) rather than
        # one reading; best/worst ride along, and the link-bandwidth
        # probes below put a number on the host<->device link.
        # floor 16: the ~1.5-cycle fill/tail overhead stays <= ~10% of
        # each segment, honoring the amortization bound above
        N_SEG = 9
        seg_cycles = max(16, min(20, int(3 * TARGET_SECONDS / per_cycle)))
        seg_rates = []
        seg_elapsed = []
        prep_s = []
        totals_before = prof.totals()  # exclude warmup/probe cycles
        link_up, link_down = probe_link_MBps()  # same-run link weather
        for _seg in range(N_SEG):
            t0 = time.perf_counter()
            run(seg_cycles, w_base, depth=depth, prep_s=prep_s)
            seg_elapsed.append(time.perf_counter() - t0)
            seg_rates.append(
                seg_cycles * K_SERVE * BATCH_WIDTH / seg_elapsed[-1])
            w_base += seg_cycles * K_SERVE
        link_up2, link_down2 = probe_link_MBps()  # weather after, too
        seg_sorted = sorted(seg_rates)
        serving_rate = seg_sorted[N_SEG // 2]  # median of 9
        cycles = N_SEG * seg_cycles
        serving_elapsed = sum(seg_elapsed)  # measured, not back-computed

        # Latency decomposition (VERDICT r3 item 8, re-derived r14): two
        # Profiler totals() snapshots around the measured segments feed
        # obs/profile.serving_decomposition() — the SAME arithmetic the
        # live /v1/debug/profile endpoint uses, so offline and live
        # numbers cannot drift apart. readback is measured in the drainer
        # (device + link jointly), link_s_est is the residual.
        totals_after = prof.totals()
        dec_per_cycle = K_SERVE * BATCH_WIDTH
        host_s = float(np.mean(prep_s)) if prep_s else 0.0
        # Link-normalized figure (VERDICT r4 item 2): what the same-run
        # measured link bandwidth predicts for a link-bound pipeline at
        # 4 B/decision up + 8 B/decision down, capped by the measured
        # host-prep and device tiers. A serving median far below this
        # number is a framework regression; a median near it is the link.
        bw_up = max(link_up, link_up2) * 1e6
        bw_down = max(link_down, link_down2) * 1e6
        link_s_per_dec = 4.0 / bw_up + 8.0 / bw_down
        link_pred = 1.0 / max(link_s_per_dec, 1e-12)
        host_pred = dec_per_cycle / host_s if host_s > 0 else float("inf")
        norm_rate = min(link_pred, host_pred,
                        decisions_per_sec)  # device tier caps the rest
        serving_row = {
            "serving_path_decisions_per_sec": round(serving_rate, 1),
            "serving_path_scope":
                "keydir(10M resident)+columnar prep+LEAN staging "
                f"(4 B/dec up, 8 back)+kernel+demux, {K_SERVE} windows/"
                f"transfer, {depth} cycles in flight (auto-tuned; see "
                "link_normalized_decisions_per_sec)",
            "serving_segment_rates": [round(r, 1) for r in seg_rates],
            "serving_segments": {
                "best": round(seg_sorted[-1], 1),
                "median": round(serving_rate, 1),
                "worst": round(seg_sorted[0], 1),
                "n": N_SEG,
            },
            "link_bandwidth_MBps": {
                "up_before": round(link_up, 2),
                "down_before": round(link_down, 2),
                "up_after": round(link_up2, 2),
                "down_after": round(link_down2, 2),
            },
            "link_normalized_decisions_per_sec": round(norm_rate, 1),
            # the ~4 KB config table ships once per config change, not
            # per cycle — excluded from the steady-state byte figures.
            # derivation_version 2 = profiler-derived (bench_check only
            # gates decomposition keys between same-version rounds).
            "serving_decomposition": {
                **{k: round(v, 4) if isinstance(v, float) else v
                   for k, v in serving_decomposition(
                       totals_before, totals_after, cycles,
                       serving_elapsed,
                       upload_bytes=dec_per_cycle * 4 * cycles,
                       download_bytes=dec_per_cycle * 8 * cycles,
                       decisions=dec_per_cycle * cycles).items()},
                "derivation_version": 2,
            },
        }

    # ---- PRODUCT path: the shipped BackendCombiner serving loop ------------
    # The depth-N pipelined combiner (service/combiner.py) driving the SAME
    # 10M-key engine through real submit() calls — request objects in,
    # RateLimitResp objects out, the exact path gRPC/peer traffic takes.
    # Probes cycles-in-flight {1, 3, 6} (1 = the old lock-step combiner);
    # the ≥2 depths overlap host prep + H2D + device + D2H of DIFFERENT
    # window groups, which is bench's serving-loop structure productized.
    product_row = {}
    if eng.supports_columnar():
        try:
            product_row = _product_combiner_bench(eng)
        except Exception as e:  # noqa: BLE001 — report, don't die
            _PHASE_FAILURES.append(str(e))
            product_row = {"product_combiner": {"error": str(e)}}

    # ---- columnar wire path: lock-step vs the depth-N pipeline -------------
    # The zero-object owner path peer hops and standalone public traffic
    # ride (service/peerlink.py _columnar_chunk): PR 3 gives it the same
    # launch/collect pipeline the object path gained in PR 2. BENCH_r07
    # records the depth probe; acceptance is pipelined >= 1.5x lock-step.
    columnar_row = {}
    if eng.supports_columnar():
        try:
            columnar_row = _columnar_pipeline_bench(eng)
        except Exception as e:  # noqa: BLE001 — report, don't die
            _PHASE_FAILURES.append(str(e))
            columnar_row = {"columnar_pipeline": {"error": str(e)}}

    # ---- overload: admission + deadline shedding vs the queueing baseline
    # Offered load at ~2x measured capacity through a real Instance;
    # BENCH_r08 records goodput, shed rate, and accepted p99 for the
    # admission run vs the no-admission baseline (PR 5's acceptance row).
    try:
        overload_row = _overload_bench(eng)
    except Exception as e:  # noqa: BLE001 — report, don't die
        _PHASE_FAILURES.append(str(e))
        overload_row = {"overload": {"error": str(e)}}

    # ---- skew: Zipf-head traffic vs the hot-key lease tier -----------------
    # A real 2-node loopback cluster under Zipf-1.1 load; BENCH_r09 records
    # client p99 + hot-owner work share for uniform / leases-off / leases-on
    # (opt-in via --skew: the cluster boot pays two engine warmups).
    skew_row = {}
    if "--skew" in sys.argv:
        try:
            skew_row = _skew_bench()
        except Exception as e:  # noqa: BLE001 — report, don't die
            _PHASE_FAILURES.append(str(e))
            skew_row = {"skew": {"error": str(e)}}

    # ---- wire contract v2: partial posts vs the v1 whole-frame barrier ----
    # A real loopback peerlink client/server pair, closed loop with frames
    # in flight; BENCH_r10 records v1 vs v2 decisions/s plus the negotiated
    # version and the server's partial-post/boundary-stall counters
    # (opt-in via --wire; acceptance is v2 >= 1.3x the v1 pipelined row).
    wire_row = {}
    if "--wire" in sys.argv:
        try:
            wire_row = _wire_bench()
        except Exception as e:  # noqa: BLE001 — report, don't die
            _PHASE_FAILURES.append(str(e))
            wire_row = {"wire": {"error": str(e)}}

    # ---- live resharding: 1M-row handoff duration + importer impact ----
    # A real 2-node loopback cluster; BENCH_r13 records evacuate() wall
    # clock, rows/s, and the importer's foreground p50/p99 quiet vs
    # mid-handoff (opt-in via --reshard: staging 1M rows costs ~a minute).
    reshard_row = {}
    if "--reshard" in sys.argv:
        try:
            reshard_row = _reshard_bench()
        except Exception as e:  # noqa: BLE001 — report, don't die
            _PHASE_FAILURES.append(str(e))
            reshard_row = {"reshard": {"error": str(e)}}

    # ---- observability plane: flight recorder on vs the escape hatch ------
    # Single-node serving with the recorder enabled vs disabled on the same
    # Instance; BENCH_r11 records the overhead (acceptance <= 2%) plus the
    # anomaly detector sweep cost.
    try:
        obs_row = _obs_bench()
    except Exception as e:  # noqa: BLE001 — report, don't die
        _PHASE_FAILURES.append(str(e))
        obs_row = {"observability": {"error": str(e)}}

    # ---- capacity cartography: history ticker + keyspace harvest ----------
    # Single-node serving with the metrics-history tick in-band vs the
    # GUBER_HISTORY=0 hatch, plus directly-timed tick/harvest costs
    # duty-cycled at production cadence (acceptance: amortized <= 2%).
    try:
        carto_row = _cartography_bench()
    except Exception as e:  # noqa: BLE001 — report, don't die
        _PHASE_FAILURES.append(str(e))
        carto_row = {"cartography": {"error": str(e)}}

    # ---- traffic-shape capture: /v1/debug/capture assembly cost -----------
    # Same single-node Instance; one in-band capture per chunk (stress
    # ceiling) plus the direct per-capture cost duty-cycled at a
    # one-capture-per-minute operator cadence (acceptance: amortized <= 2%).
    try:
        capture_row = _capture_bench()
    except Exception as e:  # noqa: BLE001 — report, don't die
        _PHASE_FAILURES.append(str(e))
        capture_row = {"capture": {"error": str(e)}}

    # ---- scenario atlas: seeded traffic shapes judged by the obs plane ----
    # Every named scenario runs its short profile against a fresh
    # in-process cluster; verdict_pass gates hard in bench_check
    # (opt-in via --scenarios: six cluster boots cost ~a minute).
    scenarios_row = {}
    if "--scenarios" in sys.argv:
        try:
            scenarios_row = _scenarios_bench()
        except Exception as e:  # noqa: BLE001 — report, don't die
            _PHASE_FAILURES.append(str(e))
            scenarios_row = {"scenarios": {"error": str(e)}}

    # ---- profiling plane: serving-cycle profiler on vs GUBER_PROFILE=0 ----
    # Single-node serving with the cycle profiler enabled vs the escape
    # hatch on the same Instance; BENCH_r14 records the overhead
    # (acceptance <= 2%, target 0.5%) plus per-observe and endpoint costs.
    try:
        profile_row = _profile_bench()
    except Exception as e:  # noqa: BLE001 — report, don't die
        _PHASE_FAILURES.append(str(e))
        profile_row = {"profiler": {"error": str(e)}}

    # ---- decision ledger: attribution hooks on vs GUBER_LEDGER=0 ----------
    # Single-node serving with the ledger parking attribution columns vs
    # the escape hatch on the same Instance; BENCH_r17 records the
    # overhead (acceptance <= 2%) plus the off-path audit cost
    # duty-cycled at the 60 s harvest cadence.
    try:
        ledger_row = _ledger_bench()
    except Exception as e:  # noqa: BLE001 — report, don't die
        _PHASE_FAILURES.append(str(e))
        ledger_row = {"ledger": {"error": str(e)}}

    # ---- lockmap runtime witness: armed vs production-default locks -------
    # Two identical single-node Instances (the witness wraps locks at
    # construction, so the hatch can't flip live); BENCH_r16 records the
    # overhead tier-1 pays for running the whole suite order-checked
    # (acceptance <= 30%, ~26% measured; production pays zero via the
    # off hatch — see _witness_bench's docstring for why the floor is
    # interpreter call overhead, not stack capture).
    try:
        witness_row = _witness_bench()
    except Exception as e:  # noqa: BLE001 — report, don't die
        _PHASE_FAILURES.append(str(e))
        witness_row = {"lock_witness": {"error": str(e)}}

    # trace-derived serving-stack phase split (never fails the bench)
    try:
        phases = phase_breakdown()
    except Exception as e:  # noqa: BLE001
        _PHASE_FAILURES.append(str(e))
        phases = {"error": str(e)}

    print(
        json.dumps(
            {
                "metric": METRIC,
                "value": round(decisions_per_sec, 1),
                **serving_row,
                **product_row,
                **columnar_row,
                **overload_row,
                **skew_row,
                **wire_row,
                **reshard_row,
                **obs_row,
                **carto_row,
                **capture_row,
                **scenarios_row,
                **profile_row,
                **ledger_row,
                **witness_row,
                **_multichip_section(),
                "phase_breakdown_ms": phases,
                "unit": UNIT,
                "vs_baseline": round(decisions_per_sec / REFERENCE_BASELINE_RPS, 2),
                "batch_width": BATCH_WIDTH,
                "scan_k": SCAN_K,
                "table_capacity": TABLE_CAPACITY,
                "single_dispatch_decisions_per_sec": round(single_dispatch, 1),
                "compact_staging_decisions_per_sec": round(compact_rate, 1),
                "window_p50_ms": round(float(np.percentile(lat, 50) * 1e3), 3),
                "window_p99_ms": round(float(np.percentile(lat, 99) * 1e3), 3),
                "latency_samples": lat_iters,  # p99 is ~max at small counts
                "iters": iters,
                "device": str(jax.devices()[0]),
                "donated": donate,
                "completion_barrier": "data-dependent fetch",
                # dispatch-only rate, for reference: enqueue can run
                # ahead of completion
                "enqueue_decisions_per_sec": round(enqueue_rate, 1),
            }
        )
    )
    if _PHASE_FAILURES:
        print(f"{len(_PHASE_FAILURES)} extra phase(s) failed: "
              f"{_PHASE_FAILURES}", file=sys.stderr, flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()

"""Fault-injection soak: a live cluster under sustained concurrent load
while nodes are killed and restarted.

The reference's fault story is a one-shot test (stop 5 of 6 instances,
assert unhealthy, restart — functional_test.go:507-569). This harness runs
the same machinery continuously: worker threads hammer every node with
mixed traffic while a chaos thread stops and restarts instances on their
original ports, and the whole run is judged on invariants rather than
scripted steps:

- SAFETY (never violated): for every key epoch — the life of one bucket
  between state losses — admitted hits never exceed the limit. Killing a
  node loses its buckets (the reference's accepted tradeoff,
  architecture.md:5-11), which RESETS an epoch, never inflates one.
- LIVENESS: errors are allowed only while a node is down (connection
  refused / deadline toward the dead owner); after the last restart the
  cluster must settle back to fully-successful traffic.
- RECOVERY: keys owned by a killed node come back fresh (full limit) and
  drain correctly again.

Usage: python scripts/soak.py [--seconds 30] [--nodes 4] [--threads 8]
Exit code 0 = all invariants held; prints one JSON line per phase.
"""

from __future__ import annotations

import argparse
import collections
import json
import random
import sys
import threading
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser("soak")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--keys", type=int, default=24)
    ap.add_argument("--limit", type=int, default=1000)
    ap.add_argument("--chaos-period", type=float, default=3.0,
                    help="seconds between kill/restart cycles")
    args = ap.parse_args(argv)

    import os

    import jax

    # the ambient device (JAX_PLATFORMS picks another); SOAK_PLATFORM
    # still pins one for this script alone
    if os.environ.get("SOAK_PLATFORM"):
        jax.config.update("jax_platforms", os.environ["SOAK_PLATFORM"])
    print(f"soak platform: {jax.devices()[0].platform} "
          f"({jax.devices()[0].device_kind})", flush=True)

    import grpc

    from gubernator_tpu.cluster.harness import LocalCluster
    from gubernator_tpu.service.grpc_api import dial_v1
    from gubernator_tpu.service.pb import gubernator_pb2 as pb
    from gubernator_tpu.types import Behavior

    cluster = LocalCluster().start(args.nodes)
    keys = [f"soak_{i}" for i in range(args.keys)]
    # ~25% of traffic drives Behavior=GLOBAL keys — the reference's own
    # fault test targets GLOBAL (functional_test.go:507-569); judged by
    # post-chaos convergence, not per-epoch admission (eventual consistency
    # admits bounded overshoot by design, PARITY.md #3)
    gkeys = [f"gsoak_{i}" for i in range(max(2, args.keys // 4))]
    stop = threading.Event()
    chaos_done = threading.Event()
    settled = threading.Event()  # 2s after the last restart: reconnect grace
    lock = threading.Lock()
    # admissions per (key, reset_time) epoch — see the SAFETY note below
    admitted = collections.Counter()
    violations = []
    errors_during_chaos = 0
    errors_after_chaos = 0
    error_samples = []
    total = 0

    def worker(wid: int):
        nonlocal errors_during_chaos, errors_after_chaos, total
        rng = random.Random(wid)
        while not stop.is_set():
            addr = cluster.instances[rng.randrange(args.nodes)].address
            is_global = rng.random() < 0.25
            key = rng.choice(gkeys if is_global else keys)
            behavior = int(Behavior.GLOBAL) if is_global else 0
            try:
                stub = dial_v1(addr)
                r = stub.GetRateLimits(pb.GetRateLimitsReq(requests=[
                    pb.RateLimitReq(name="soak", unique_key=key, hits=1,
                                    limit=args.limit, duration=3_600_000,
                                    behavior=behavior)
                ]), timeout=10,
                    # settle-phase liveness is judged on the serving stack,
                    # not on grpc client reconnect races
                    wait_for_ready=chaos_done.is_set()).responses[0]
            except grpc.RpcError as e:
                with lock:
                    if settled.is_set():
                        errors_after_chaos += 1
                        if len(error_samples) < 5:
                            error_samples.append(f"rpc:{e.code()}")
                    else:
                        errors_during_chaos += 1
                continue
            with lock:
                total += 1
                if r.error:
                    if settled.is_set():
                        errors_after_chaos += 1
                        if len(error_samples) < 5:
                            error_samples.append(r.error[:120])
                    else:
                        errors_during_chaos += 1
                elif r.status == 0 and not is_global:
                    # SAFETY: within one epoch, admissions <= limit. The
                    # epoch is identified by reset_time — a restarted owner
                    # recreates the bucket with a fresh CreatedAt, so its
                    # reset_time moves. Counting per (key, reset_time) is
                    # immune to response-reordering races that a
                    # "remaining jumped back up" heuristic trips over:
                    # admission order and response-processing order differ
                    # under concurrency.
                    epoch = (key, r.reset_time)
                    admitted[epoch] += 1
                    if admitted[epoch] > args.limit:
                        violations.append(
                            f"{key}@{r.reset_time}: "
                            f"{admitted[epoch]} admissions > limit")

    def chaos():
        rng = random.Random(99)
        deadline = time.monotonic() + args.seconds * 0.7
        cycles = 0
        while time.monotonic() < deadline and not stop.is_set():
            time.sleep(args.chaos_period)
            idx = rng.randrange(args.nodes)
            victim = cluster.instances[idx]
            port = int(victim.address.rsplit(":", 1)[1])
            cluster.stop_instance_at(idx)
            time.sleep(args.chaos_period / 2)
            cluster.start_instance(fixed_port=port)
            cluster.sync_peers()
            cycles += 1
        chaos_done.set()
        print(json.dumps({"phase": "chaos", "kill_restart_cycles": cycles}),
              flush=True)

    workers = [threading.Thread(target=worker, args=(w,))
               for w in range(args.threads)]
    chaos_thread = threading.Thread(target=chaos)
    for t in workers:
        t.start()
    chaos_thread.start()

    chaos_thread.join()
    time.sleep(2.0)  # reconnect grace: bounded backoff reconnects within ~1s
    settled.set()
    settle = time.monotonic()
    # settle phase: post-chaos traffic must succeed
    with lock:
        errors_after_chaos = 0
    while time.monotonic() - settle < max(args.seconds * 0.3, 8.0):
        time.sleep(0.5)
    stop.set()
    for t in workers:
        t.join(timeout=30)

    # CONVERGENCE: with traffic quiesced, every node's view of every GLOBAL
    # key — owner authoritative or non-owner mirror — must agree. Broadcasts
    # are request-triggered, so a key idle through the settle phase can hold
    # a legitimately stale mirror: the first probe pass touches every
    # (key, node) pair (a hits=0 GLOBAL request queues through the async
    # pipelines and the owner rebroadcasts), then a few 50 ms test sync
    # windows elapse, then the judged pass runs. Any error — application or
    # RPC, uniform or not — fails the check; ignoring them could false-pass
    # a cluster-wide GLOBAL breakage as "converged".
    def probe(key):
        views = {}
        for ci in cluster.instances:
            try:
                r = dial_v1(ci.address).GetRateLimits(
                    pb.GetRateLimitsReq(requests=[
                        pb.RateLimitReq(name="soak", unique_key=key, hits=0,
                                        limit=args.limit,
                                        duration=3_600_000,
                                        behavior=int(Behavior.GLOBAL))
                    ]), timeout=10, wait_for_ready=True).responses[0]
                views[ci.address] = (f"err:{r.error[:80]}" if r.error
                                     else r.remaining)
            except grpc.RpcError as e:
                views[ci.address] = f"rpc:{e.code()}"
        return views

    global_divergence = []
    for key in gkeys:
        probe(key)  # refresh pass: trigger owner rebroadcast to every peer
    time.sleep(1.0)
    for key in gkeys:
        views = probe(key)
        errs = [v for v in views.values() if isinstance(v, str)]
        if errs or len(set(views.values())) > 1:
            global_divergence.append({key: views})
    cluster.stop()

    ok = (not violations and errors_after_chaos == 0
          and not global_divergence)
    print(json.dumps({
        "phase": "result",
        "ok": ok,
        "total_decisions": total,
        "admission_violations": violations[:5],
        "errors_during_chaos": errors_during_chaos,
        "errors_after_chaos": errors_after_chaos,
        "error_samples": error_samples,
        "global_divergence": global_divergence[:3],
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

"""Serving-stack benchmark suite — ports of the reference's shipped
benchmarks plus BASELINE.json's scenario configs, against a real in-process
loopback cluster (the reference's own rig: benchmark_test.go:28-135 over
cluster/cluster.go).

Scenarios:
  get_rate_limit             BenchmarkServer_GetRateLimit (single-req RPC)
  get_peer_no_batching       BenchmarkServer_GetPeerRateLimitNoBatching
  health_check               BenchmarkServer_Ping
  thundering_herd            BenchmarkServer_ThunderingHeard (100-wide fanout)
  thundering_herd_mp         same herd from 4 client PROCESSES (server capacity,
                             not the bench process's GIL)
  grpc_native_wire_rps       the native gRPC/HTTP/2 front under a lean raw-h2
                             pipelined client (h2load methodology): the
                             wire-compatible surface's server capacity
  grpc_native_unbatched_rps  same front, pipelined grpcio client futures
  grpc_native_herd_mp        same front, 4-process grpcio herd (1-node)
  grpc_native_routed_herd_mp same herd against the multi-node cluster (full
                             routing: most keys forward to their owner)
  leaky_bucket               LEAKY_BUCKET drain (BASELINE.json configs[1])
  global_mode                Behavior=GLOBAL aggregation (configs[2])
  gregorian                  DURATION_IS_GREGORIAN resets (configs[3])
  multi_region               2-DC cluster, MULTI_REGION hits (configs[4])

Each scenario prints one JSON line {"bench", "ops_per_s", "p50_ms",
"p99_ms", "n", ...}. The suite runs on the ambient device (what JAX finds,
or what JAX_PLATFORMS names) and prints the platform it ran on first;
--platform=cpu pins JAX to the CPU to look at the gRPC/batching/host path
alone, the way the reference's Go benchmarks do.

Usage: python scripts/bench_suite.py [--seconds 2.0] [--nodes 3]
       [--only name[,name...]] [--platform cpu|default]
"""

from __future__ import annotations

import argparse
import json
import os
import random
import string
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _percentile(sorted_ms, q: float) -> float:
    if not sorted_ms:
        return 0.0
    idx = min(len(sorted_ms) - 1, int(q * (len(sorted_ms) - 1) + 0.5))
    return sorted_ms[idx]


def _rand_key(rng, n=10) -> str:
    # reference: client.go RandomString(10)
    return "".join(rng.choice(string.ascii_lowercase) for _ in range(n))


def run_serial(fn, seconds: float, warmup: int = 50):
    """b.N-style loop: run fn for `seconds` after warmup; returns stats."""
    for _ in range(warmup):
        fn()
    lat = []
    t_end = time.perf_counter() + seconds
    t0 = time.perf_counter()
    while time.perf_counter() < t_end:
        s = time.perf_counter()
        fn()
        lat.append((time.perf_counter() - s) * 1e3)
    elapsed = time.perf_counter() - t0
    lat.sort()
    return {
        "ops_per_s": round(len(lat) / elapsed, 1),
        "p50_ms": round(_percentile(lat, 0.50), 3),
        "p99_ms": round(_percentile(lat, 0.99), 3),
        "n": len(lat),
    }


def run_fanout(fn, seconds: float, width: int = 100, warmup: int = 50):
    """ThunderingHeard rig: `width` concurrent callers
    (reference: benchmark_test.go:108-135 syncutil.NewFanOut(100))."""
    for _ in range(warmup):
        fn()
    lat = []
    pool = ThreadPoolExecutor(max_workers=width)
    t_end = time.perf_counter() + seconds

    def timed():
        s = time.perf_counter()
        fn()
        return (time.perf_counter() - s) * 1e3

    t0 = time.perf_counter()
    futures = [pool.submit(timed) for _ in range(width)]
    while True:
        done, futures = futures, []
        for f in done:
            lat.append(f.result())
            if time.perf_counter() < t_end:
                futures.append(pool.submit(timed))
        if not futures:
            break
    elapsed = time.perf_counter() - t0
    pool.shutdown()
    lat.sort()
    return {
        "ops_per_s": round(len(lat) / elapsed, 1),
        "p50_ms": round(_percentile(lat, 0.50), 3),
        "p99_ms": round(_percentile(lat, 0.99), 3),
        "n": len(lat),
        "fanout": width,
    }


def _herd_worker(address: str, seconds: float, threads: int, seed: int, out_q):
    """One client PROCESS of the multiprocess herd (spawned): `threads`
    concurrent single-request callers against `address` for `seconds`.
    Runs in its own interpreter so the parent's GIL stops capping the
    offered load — the in-process thread herd (run_fanout) measures the
    client as much as the server."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # never touch a device
    import time as _time
    from concurrent.futures import ThreadPoolExecutor as _Pool

    from gubernator_tpu.client import V1Client
    from gubernator_tpu.types import RateLimitReq

    try:
        client = V1Client(address)

        def loop(tid: int):
            rng = random.Random(seed * 1000 + tid)
            lat = []
            mk = lambda: RateLimitReq(
                name="get_rate_limit_benchmark", unique_key=_rand_key(rng),
                hits=1, limit=10, duration=5_000)
            client.get_rate_limits([mk()], timeout=30)  # connect + warm
            t_end = _time.perf_counter() + seconds
            while _time.perf_counter() < t_end:
                s = _time.perf_counter()
                client.get_rate_limits([mk()], timeout=30)
                lat.append((_time.perf_counter() - s) * 1e3)
            return lat

        out = []
        t0 = _time.perf_counter()
        with _Pool(max_workers=threads) as pool:
            for chunk in pool.map(loop, range(threads)):
                out.extend(chunk)
        out_q.put((out, _time.perf_counter() - t0))
    except Exception as e:  # noqa: BLE001 — a dead worker must not wedge
        out_q.put(("error", repr(e)))  # the parent (cf. bench.py watchdog)


def run_herd_mp(address: str, seconds: float, procs: int = 4,
                threads: int = 25):
    """ThunderingHeard with the client herd spread over `procs` real
    processes (procs*threads concurrent callers) so the measurement is
    server capacity, not the benchmarking process's GIL."""
    import multiprocessing as mp

    import queue as _queue

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    workers = [
        ctx.Process(target=_herd_worker,
                    args=(address, seconds, threads, p, q), daemon=True)
        for p in range(procs)
    ]
    for w in workers:
        w.start()
    lat, spans, failures = [], [], []
    pending = len(workers)
    deadline = time.monotonic() + seconds + 90
    while pending and time.monotonic() < deadline:
        try:
            item = q.get(timeout=1.0)
        except _queue.Empty:
            # a worker that died without reporting must not wedge the suite
            if not any(w.is_alive() for w in workers):
                break
            continue
        pending -= 1
        if isinstance(item, tuple) and item and item[0] == "error":
            failures.append(item[1])
        else:
            chunk, span = item
            lat.extend(chunk)
            spans.append(span)
    for w in workers:
        w.join(timeout=10)
        if w.is_alive():
            w.terminate()
    lat.sort()
    # completions over the measured window, same methodology as
    # run_serial/run_fanout (dividing by nominal `seconds` would count
    # requests still in flight at the cutoff)
    elapsed = max(spans) if spans else seconds
    out = {
        "ops_per_s": round(len(lat) / elapsed, 1),
        "p50_ms": round(_percentile(lat, 0.50), 3),
        "p99_ms": round(_percentile(lat, 0.99), 3),
        "n": len(lat),
        "fanout": procs * threads,
        "client_procs": procs,
    }
    if failures or pending:
        out["worker_failures"] = len(failures) + pending
        out["first_failure"] = failures[0] if failures else "no report"
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--nodes", type=int, default=3)
    ap.add_argument("--only", type=str, default="")
    ap.add_argument("--platform", choices=["cpu", "default"],
                    default="default")
    args = ap.parse_args(argv)

    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    print(json.dumps({"platform": jax.devices()[0].platform,
                      "device_kind": jax.devices()[0].device_kind,
                      "device_count": len(jax.devices())}), flush=True)

    from gubernator_tpu.client import V1Client
    from gubernator_tpu.cluster.harness import LocalCluster
    from gubernator_tpu.service.peer_client import PeerClient
    from gubernator_tpu.types import Algorithm, Behavior, PeerInfo, RateLimitReq
    from gubernator_tpu.utils.gregorian import GREGORIAN_MINUTES

    rng = random.Random(42)

    def req(name, key, **kw):
        defaults = dict(hits=1, limit=10, duration=5_000)
        defaults.update(kw)
        return RateLimitReq(name=name, unique_key=key, **defaults)

    print(
        f"# bench_suite: {args.nodes}-node loopback cluster, "
        f"{args.seconds:.1f}s/scenario, platform={args.platform}",
        file=sys.stderr,
    )
    cluster = LocalCluster().start(
        args.nodes, datacenters=["dc-a"] * (args.nodes - 1) + ["dc-b"]
    )
    # wire peerlink between the nodes, as the daemon does by default
    # (GUBER_PEER_LINK_OFFSET=1000): inter-node forwarding rides the native
    # transport; scenarios that fail to wire it fall back to gRPC silently
    node_links = []
    try:
        from gubernator_tpu.cluster.harness import wire_peerlink

        node_links = wire_peerlink(cluster)
        print(f"# peerlink between nodes: "
              f"{'wired' if node_links else 'DISABLED'}", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — bench must run without native
        print(f"# peerlink between nodes unavailable: {e}", file=sys.stderr)
    try:
        client = V1Client(rng.choice(cluster.instances).address)

        def bench_get_rate_limit():
            # reference: benchmark_test.go:53-77
            return run_serial(
                lambda: client.get_rate_limits(
                    [req("get_rate_limit_benchmark", _rand_key(rng))]
                ),
                args.seconds,
            )

        def bench_get_peer_no_batching():
            # reference: benchmark_test.go:28-51 — direct PeerClient unary
            ci = rng.choice(cluster.instances)
            peer = PeerClient(
                cluster.instances[0].instance.conf.behaviors,
                PeerInfo(address=ci.address, datacenter=ci.datacenter),
            )
            try:
                return run_serial(
                    lambda: peer.get_peer_rate_limit(
                        req(
                            "get_peer_rate_limits_benchmark",
                            _rand_key(rng),
                            behavior=Behavior.NO_BATCHING,
                            duration=5,
                        )
                    ),
                    args.seconds,
                )
            finally:
                peer.shutdown()

        def bench_get_rate_limit_batch():
            # the design point: clients batch (reference README.md:113-115 —
            # production traffic rides 500µs windows up to 1000 wide).
            # ops_per_s here counts CALLS; requests/s = ops_per_s * 100.
            def call():
                client.get_rate_limits(
                    [
                        req("get_rate_limit_benchmark", _rand_key(rng))
                        for _ in range(100)
                    ],
                    timeout=30,
                )

            stats = run_serial(call, args.seconds, warmup=10)
            stats["requests_per_s"] = round(stats["ops_per_s"] * 100, 1)
            return stats

        def bench_health_check():
            # reference: benchmark_test.go:80-97
            return run_serial(lambda: client.health_check(), args.seconds)

        def bench_thundering_herd():
            # reference: benchmark_test.go:108-135
            return run_fanout(
                lambda: client.get_rate_limits(
                    [req("get_rate_limit_benchmark", _rand_key(rng))]
                ),
                args.seconds,
            )

        def bench_thundering_herd_mp():
            # same herd, client spread over real processes: server capacity
            return run_herd_mp(
                rng.choice(cluster.instances).address, args.seconds)

        def bench_leaky_bucket():
            return run_serial(
                lambda: client.get_rate_limits(
                    [
                        req(
                            "leaky_benchmark",
                            _rand_key(rng),
                            algorithm=Algorithm.LEAKY_BUCKET,
                            limit=100,
                            duration=60_000,
                        )
                    ]
                ),
                args.seconds,
            )

        def bench_global_mode():
            return run_serial(
                lambda: client.get_rate_limits(
                    [
                        req(
                            "global_benchmark",
                            _rand_key(rng),
                            behavior=Behavior.GLOBAL,
                            limit=1_000_000,
                        )
                    ]
                ),
                args.seconds,
            )

        def bench_gregorian():
            return run_serial(
                lambda: client.get_rate_limits(
                    [
                        req(
                            "gregorian_benchmark",
                            _rand_key(rng),
                            behavior=Behavior.DURATION_IS_GREGORIAN,
                            duration=GREGORIAN_MINUTES,
                            limit=1_000_000,
                        )
                    ]
                ),
                args.seconds,
            )

        def bench_peerlink_hop():
            # the native peer transport vs get_peer_no_batching's gRPC hop
            # (VERDICT r1 item 1: the reference's forwarded hop is ~30 µs,
            # README.md:104; python gRPC pays ~0.4-0.8 ms)
            from gubernator_tpu.service.peerlink import (
                METHOD_GET_PEER_RATE_LIMITS,
                PeerLinkClient,
                PeerLinkService,
            )

            ci = rng.choice(cluster.instances)
            svc = PeerLinkService(ci.instance, port=0)
            cli = PeerLinkClient(f"127.0.0.1:{svc.port}")
            try:
                return run_serial(
                    lambda: cli.call(
                        METHOD_GET_PEER_RATE_LIMITS,
                        [req("peerlink_benchmark", _rand_key(rng),
                             duration=5)],
                        5.0,
                    ),
                    args.seconds,
                )
            finally:
                cli.close()
                svc.close()

        def bench_peerlink_unbatched_rps():
            # server capacity under pipelined UNBATCHED load: every RPC is
            # one single-request frame; WINDOW outstanding keeps the link
            # busy the way a fleet of independent callers would. Done bar
            # (VERDICT r1 item 1): >= 20k unbatched RPC/s/node.
            from gubernator_tpu.service import peerlink as pl

            ci = rng.choice(cluster.instances)
            svc = pl.PeerLinkService(ci.instance, port=0)
            cli = pl.PeerLinkClient(f"127.0.0.1:{svc.port}")
            try:
                WINDOW = 64
                done = 0
                inflight = []
                deadline = time.perf_counter() + args.seconds
                t0 = time.perf_counter()
                while time.perf_counter() < deadline or inflight:
                    while (len(inflight) < WINDOW
                           and time.perf_counter() < deadline):
                        fut, _rid = cli.call_async(
                            pl.METHOD_GET_PEER_RATE_LIMITS,
                            [req("peerlink_rps", _rand_key(rng), duration=5)])
                        inflight.append(fut)
                    inflight.pop(0).result(timeout=30.0)
                    done += 1
                el = time.perf_counter() - t0
                return {"ops": done, "ops_per_s": round(done / el, 1),
                        "pipeline_window": WINDOW}
            finally:
                cli.close()
                svc.close()

        def bench_peerlink_herd():
            # VERDICT r1 item 5 done bar: p99 < 10 ms at 100 concurrent
            # single-request callers. Over gRPC the herd queues behind the
            # ~2.3k RPC/s GIL-bound tier (Little's law: 100/2300 = 43 ms
            # p50); over peerlink the same herd aggregates server-side.
            from gubernator_tpu.service.peerlink import (
                METHOD_GET_RATE_LIMITS,
                PeerLinkClient,
                PeerLinkService,
            )

            ci = rng.choice(cluster.instances)
            svc = PeerLinkService(ci.instance, port=0)
            clients = [PeerLinkClient(f"127.0.0.1:{svc.port}")
                       for _ in range(8)]  # 100 callers share 8 links
            k = 0
            try:
                def call():
                    nonlocal k
                    k += 1
                    clients[k % len(clients)].call(
                        METHOD_GET_RATE_LIMITS,
                        [req("peerlink_herd", _rand_key(rng))], 30.0)

                return run_fanout(call, args.seconds)
            finally:
                for c in clients:
                    c.close()
                svc.close()

        def bench_peerlink_batch100():
            # VERDICT r1 item 5 done bar: batched clients see p99 < 2 ms
            from gubernator_tpu.service.peerlink import (
                METHOD_GET_RATE_LIMITS,
                PeerLinkClient,
                PeerLinkService,
            )

            ci = rng.choice(cluster.instances)
            svc = PeerLinkService(ci.instance, port=0)
            cli = PeerLinkClient(f"127.0.0.1:{svc.port}")
            try:
                def call():
                    cli.call(
                        METHOD_GET_RATE_LIMITS,
                        [req("peerlink_b100", _rand_key(rng))
                         for _ in range(100)], 30.0)

                stats = run_serial(call, args.seconds, warmup=10)
                stats["requests_per_s"] = round(stats["ops_per_s"] * 100, 1)
                return stats
            finally:
                cli.close()
                svc.close()

        def _start_grpc_front(ci):
            from gubernator_tpu.service.peerlink import PeerLinkService

            return PeerLinkService(ci.instance, port=0, grpc_port=0)

        def _one_node_front():
            """A dedicated single-node instance + native gRPC front: the
            per-NODE capacity of the wire-compatible surface (the
            reference's >2k req/s/node headline is per node too,
            README.md:94-100). On one node the front's method-0 frames
            ride the zero-object columnar path end to end."""
            from gubernator_tpu.cluster.harness import LocalCluster

            one = LocalCluster().start(1)
            return one, _start_grpc_front(one.instances[0])

        def bench_grpc_native_unbatched_rps():
            # The WIRE-COMPATIBLE surface under pipelined unbatched load
            # (VERDICT r3 item 2 done bar: >= 5k RPC/s). Every call is a
            # real gRPC unary RPC from grpcio; WINDOW outstanding futures
            # keep the server busy the way independent callers would.
            import grpc as _grpc

            from gubernator_tpu.service.grpc_api import V1Stub
            from gubernator_tpu.service.pb import gubernator_pb2 as _pb

            one, svc = _one_node_front()
            ch = _grpc.insecure_channel(f"127.0.0.1:{svc.grpc_port}")
            stub = V1Stub(ch)
            try:
                def mk():
                    return _pb.GetRateLimitsReq(requests=[_pb.RateLimitReq(
                        name="grpc_native_rps", unique_key=_rand_key(rng),
                        hits=1, limit=10, duration=5_000)])

                stub.GetRateLimits(mk(), timeout=30)  # connect + warm
                WINDOW = 64
                done = 0
                inflight = []
                deadline = time.perf_counter() + args.seconds
                t0 = time.perf_counter()
                while time.perf_counter() < deadline or inflight:
                    while (len(inflight) < WINDOW
                           and time.perf_counter() < deadline):
                        inflight.append(
                            stub.GetRateLimits.future(mk(), timeout=30))
                    inflight.pop(0).result()
                    done += 1
                el = time.perf_counter() - t0
                return {"ops": done, "ops_per_s": round(done / el, 1),
                        "pipeline_window": WINDOW,
                        "native_hits": svc.native_hits()}
            finally:
                ch.close()
                svc.close()
                one.stop()

        def bench_grpc_native_herd_mp():
            # Wire-compatible gRPC herd from 4 client PROCESSES against
            # a single-node native front — per-node server capacity +
            # herd p99 on the surface existing gubernator clients speak
            # (done bar: herd p99 <= 10 ms).
            one, svc = _one_node_front()
            try:
                out = run_herd_mp(f"127.0.0.1:{svc.grpc_port}",
                                  args.seconds)
                out["native_hits"] = svc.native_hits()
                return out
            finally:
                svc.close()
                one.stop()

        def bench_grpc_native_wire_rps():
            # Server-side capacity of the wire-compatible surface with a
            # LEAN load generator (h2load methodology): a hand-rolled
            # HTTP/2 client pipelines unary gRPC calls over one
            # connection, costing ~10 µs/RPC client-side — on this 1-core
            # rig the grpcio client library costs ~0.2 ms/RPC and caps
            # the herd scenarios well below the server's capacity. The
            # bytes on the wire are exactly what a gRPC client sends.
            import socket
            import struct as _s

            from gubernator_tpu.service.pb import gubernator_pb2 as _pb

            one, svc = _one_node_front()
            sk = socket.create_connection(("127.0.0.1", svc.grpc_port))
            sk.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            try:
                def frame(t, flags, sid, payload=b""):
                    return (_s.pack(">I", len(payload))[1:]
                            + bytes([t, flags]) + _s.pack(">I", sid)
                            + payload)

                def lit(n, v):
                    return bytes([0, len(n)]) + n + bytes([len(v)]) + v

                hdrs = (lit(b":method", b"POST") + lit(b":scheme", b"http")
                        + lit(b":path", b"/pb.gubernator.V1/GetRateLimits")
                        + lit(b":authority", b"bench")
                        + lit(b"content-type", b"application/grpc")
                        + lit(b"te", b"trailers"))
                # distinct keys like every herd scenario — a tiny key
                # pool turns each pull into duplicate-key ROUNDS (one
                # kernel dispatch per duplicate) and measures that
                # instead of the serving path
                bodies = []
                for i in range(16384):
                    msg = _pb.GetRateLimitsReq(requests=[_pb.RateLimitReq(
                        name="grpc_wire", unique_key=_rand_key(rng),
                        hits=1, limit=10, duration=5_000,
                    )]).SerializeToString()
                    bodies.append(b"\x00" + _s.pack(">I", len(msg)) + msg)
                sk.sendall(b"PRI * HTTP/2.0\r\n\r\nSM\r\n\r\n"
                           + frame(4, 0, 0))
                WINDOW = 100  # the thundering-herd shape
                sid = 1
                inflight = 0
                done = 0
                consumed = 0
                buf = b""
                starts = {}
                lat = []
                sk.setblocking(False)
                deadline = time.perf_counter() + args.seconds
                t0 = time.perf_counter()
                while True:
                    now_t = time.perf_counter()
                    if now_t >= deadline and inflight == 0:
                        break
                    while inflight < WINDOW and now_t < deadline:
                        sk.setblocking(True)
                        sk.sendall(frame(1, 0x4, sid, hdrs)
                                   + frame(0, 0x1, sid,
                                           bodies[(sid >> 1) % 16384]))
                        sk.setblocking(False)
                        starts[sid] = time.perf_counter()
                        sid += 2
                        inflight += 1
                    try:
                        d = sk.recv(1 << 18)
                        if not d:
                            break
                        buf += d
                    except BlockingIOError:
                        time.sleep(0)
                    off = 0
                    now_t = time.perf_counter()
                    while len(buf) - off >= 9:
                        ln = int.from_bytes(buf[off:off + 3], "big")
                        if len(buf) - off - 9 < ln:
                            break
                        t = buf[off + 3]
                        fl = buf[off + 4]
                        if t == 0:
                            consumed += ln
                        if t == 1 and (fl & 0x1):  # trailers END_STREAM
                            rsid = int.from_bytes(
                                buf[off + 5:off + 9], "big") & 0x7fffffff
                            s0 = starts.pop(rsid, None)
                            if s0 is not None:
                                lat.append((now_t - s0) * 1e3)
                            done += 1
                            inflight -= 1
                        off += 9 + ln
                    buf = buf[off:]
                    if consumed > 32768:  # keep the server's send window fed
                        sk.setblocking(True)
                        sk.sendall(frame(8, 0, 0, _s.pack(">I", consumed)))
                        sk.setblocking(False)
                        consumed = 0
                el = time.perf_counter() - t0
                lat.sort()
                pulls = max(svc.stats["batches"], 1)
                return {"ops": done, "ops_per_s": round(done / el, 1),
                        "p50_ms": round(_percentile(lat, 0.50), 3),
                        "p99_ms": round(_percentile(lat, 0.99), 3),
                        "pipeline_window": WINDOW,
                        "items_per_pull": round(
                            svc.stats["requests"] / pulls, 1),
                        "client": "raw-h2 (h2load methodology)"}
            finally:
                sk.close()
                svc.close()
                one.stop()

        def bench_grpc_native_routed_herd_mp():
            # The same herd against a front on the SHARED multi-node
            # cluster: every RPC pays real routing (2/3 of keys forward
            # to the owner over peerlink) — the fleet-topology picture.
            ci = rng.choice(cluster.instances)
            svc = _start_grpc_front(ci)
            try:
                out = run_herd_mp(f"127.0.0.1:{svc.grpc_port}",
                                  args.seconds)
                out["native_hits"] = svc.native_hits()
                return out
            finally:
                svc.close()

        def bench_grpc_herd_fairness():
            # VERDICT r4 item 8: are the ~50 ms grpcio herd p99s a server
            # fairness problem or client-library queuing? On this 1-core
            # rig the herd processes cannot be pinned off the server's
            # core, so the discriminating experiment runs a LEAN probe
            # client (native LinkClient, ~10 µs client cost) through the
            # SAME server at low offered load DURING the grpcio herd:
            # an unfair/slow server would collapse the probe's p99 along
            # with the herd's; a fair server serving self-queued grpcio
            # clients keeps the probe fast while grpcio reports ~50 ms.
            import threading as _t

            from gubernator_tpu.service.peerlink import (
                METHOD_GET_RATE_LIMITS,
                PeerLinkClient,
            )

            ci = rng.choice(cluster.instances)
            svc = _start_grpc_front(ci)
            probe_lat = []
            stop = _t.Event()

            def probe_once(cli, r, sink):
                t0 = time.perf_counter()
                cli.call(METHOD_GET_RATE_LIMITS, r, 30.0)
                sink.append((time.perf_counter() - t0) * 1e3)

            def prober():
                cli = PeerLinkClient(f"127.0.0.1:{svc.port}")
                try:
                    r = [req("fair_probe", "probe_key", limit=1 << 30,
                             duration=3_600_000)]
                    cli.call(METHOD_GET_RATE_LIMITS, r, 30.0)  # warm
                    while not stop.is_set():
                        probe_once(cli, r, probe_lat)
                        stop.wait(0.005)  # low offered load
                finally:
                    cli.close()

            # baseline: the same probe ALONE (no herd) — the un-contended
            # floor the mixed-load numbers are read against
            base_lat = []
            cli0 = PeerLinkClient(f"127.0.0.1:{svc.port}")
            try:
                r0 = [req("fair_probe", "probe_key", limit=1 << 30,
                          duration=3_600_000)]
                cli0.call(METHOD_GET_RATE_LIMITS, r0, 30.0)
                t_end = time.perf_counter() + min(2.0, args.seconds)
                while time.perf_counter() < t_end:
                    probe_once(cli0, r0, base_lat)
                    time.sleep(0.005)
            finally:
                cli0.close()

            th = _t.Thread(target=prober, daemon=True)
            th.start()
            try:
                out = run_herd_mp(f"127.0.0.1:{svc.grpc_port}",
                                  args.seconds)
            finally:
                stop.set()
                th.join(timeout=10)
                svc.close()
            lat = sorted(probe_lat)
            base = sorted(base_lat)
            out["probe_rpcs"] = len(lat)
            out["probe_alone_p50_ms"] = round(_percentile(base, 0.50), 3)
            out["probe_alone_p99_ms"] = round(_percentile(base, 0.99), 3)
            out["probe_during_herd_p50_ms"] = round(
                _percentile(lat, 0.50), 3)
            out["probe_during_herd_p99_ms"] = round(
                _percentile(lat, 0.99), 3)
            out["client"] = "4-proc grpcio herd + concurrent lean probe"
            return out

        def bench_multi_region():
            return run_serial(
                lambda: client.get_rate_limits(
                    [
                        req(
                            "multi_region_benchmark",
                            _rand_key(rng),
                            behavior=Behavior.MULTI_REGION,
                            limit=1_000_000,
                        )
                    ]
                ),
                args.seconds,
            )

        def bench_native_lone_hop():
            # r3: 1-item peer-hop frames decided in the C++ IO thread
            # against the directory row mirror (keydir.cpp decide_one) —
            # no Python worker, no kernel dispatch. The first call misses
            # (kernel path) and seeds; the timed loop runs native.
            from gubernator_tpu.service.peerlink import (
                METHOD_GET_PEER_RATE_LIMITS,
                PeerLinkClient,
                PeerLinkService,
            )

            ci = rng.choice(cluster.instances)
            svc = PeerLinkService(ci.instance, port=0)
            cli = PeerLinkClient(f"127.0.0.1:{svc.port}")
            try:
                r = [req("native_hop", "hot", duration=3_600_000,
                         limit=1 << 40)]
                cli.call(METHOD_GET_PEER_RATE_LIMITS, r, 5.0)  # miss+seed
                out = run_serial(
                    lambda: cli.call(METHOD_GET_PEER_RATE_LIMITS, r, 5.0),
                    args.seconds)
                out["native_hits"] = svc.native_hits()
                return out
            finally:
                cli.close()
                svc.close()

        def bench_public_link_serial():
            # r3: the PUBLIC lean surface over the columnar link
            # (client.LinkClient, method 0 — full router semantics). On
            # this multi-node cluster frames take the routed object path
            # server-side; the standalone IO-thread fast path has its
            # own scenario below.
            from gubernator_tpu.client import LinkClient

            if not node_links:
                return {"skipped": "peerlink not wired"}
            # SAME entry node as bench_get_rate_limit's V1Client, so the
            # two rows compare the transports, not the key-ownership mix
            idx = next(i for i, x in enumerate(cluster.instances)
                       if x.address == client.address)
            ci = cluster.instances[idx]
            off = node_links[idx].port - int(
                ci.address.rsplit(":", 1)[1])
            cli = LinkClient(ci.address, link_offset=off)
            try:
                if cli._link is None:
                    return {"skipped": "link did not connect"}
                return run_serial(
                    lambda: cli.get_rate_limits(
                        [req("public_link", _rand_key(rng),
                             limit=1_000_000)]),
                    args.seconds)
            finally:
                cli.close()

        def bench_herd_with_store():
            # r2 verdict item 5 'done' bar: a Store no longer disables the
            # scan-coalesced dispatch. A hot-key herd (d duplicates = d
            # rounds) against a store-attached engine retires in ~d/32
            # dispatches with ONE batched read-through + write-through,
            # vs one dispatch + two hook passes PER ROUND before.
            from gubernator_tpu.models.engine import Engine as _Engine
            from gubernator_tpu.store import MockStore

            store = MockStore()
            eng = _Engine(capacity=4096, min_width=16, max_width=256,
                          store=store)
            eng.warmup()
            herd = [req("herd_store", "hot", limit=10**9,
                        duration=3_600_000) for _ in range(64)]
            out = run_serial(lambda: eng.get_rate_limits(herd),
                             args.seconds, warmup=5)
            out["req_per_s"] = round(out["ops_per_s"] * len(herd), 1)
            out["scan_rounds"] = eng.stats.rounds
            out["on_change_calls"] = store.called["on_change"]
            return out

        scenarios = {
            "get_rate_limit": bench_get_rate_limit,
            "get_rate_limit_batch100": bench_get_rate_limit_batch,
            "get_peer_no_batching": bench_get_peer_no_batching,
            "peerlink_hop": bench_peerlink_hop,
            "peerlink_unbatched_rps": bench_peerlink_unbatched_rps,
            "peerlink_herd": bench_peerlink_herd,
            "peerlink_batch100": bench_peerlink_batch100,
            "native_lone_hop": bench_native_lone_hop,
            "public_link_serial": bench_public_link_serial,
            "herd_with_store": bench_herd_with_store,
            "health_check": bench_health_check,
            "thundering_herd": bench_thundering_herd,
            "thundering_herd_mp": bench_thundering_herd_mp,
            "grpc_native_unbatched_rps": bench_grpc_native_unbatched_rps,
            "grpc_native_wire_rps": bench_grpc_native_wire_rps,
            "grpc_native_herd_mp": bench_grpc_native_herd_mp,
            "grpc_native_routed_herd_mp": bench_grpc_native_routed_herd_mp,
            "grpc_herd_fairness": bench_grpc_herd_fairness,
            "leaky_bucket": bench_leaky_bucket,
            "global_mode": bench_global_mode,
            "gregorian": bench_gregorian,
            "multi_region": bench_multi_region,
        }
        selected = (
            [s.strip() for s in args.only.split(",") if s.strip()]
            if args.only
            else list(scenarios)
        )
        unknown = [s for s in selected if s not in scenarios]
        if unknown:
            print(f"unknown scenarios: {unknown}", file=sys.stderr)
            return 2

        for name in selected:
            stats = scenarios[name]()
            print(json.dumps({"bench": name, **stats}), flush=True)
    finally:
        for svc in node_links:
            svc.close()
        cluster.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Local test cluster: `python -m gubernator_tpu.cmd.cluster_main`.

Boots an in-process 6-node cluster on fixed loopback ports and prints
"Ready" — the sentinel the cross-language client test fixtures wait for
(reference: cmd/gubernator-cluster/main.go:29-55,
python/tests/test_client.py:25-39).

With `--etcd`, membership comes from real discovery instead of injected
peer lists: an embedded etcdlite server starts first and every node runs a
full EtcdPool (register + lease + watch) against it — the closest
single-process analogue of a production etcd-discovered cluster.
"""

from __future__ import annotations

import sys
import time

from gubernator_tpu.cluster.harness import LocalCluster

DEFAULT_PORTS = [9090, 9091, 9092, 9093, 9094, 9095]


def build_cluster(ports, use_etcd: bool = False, log=None):
    """Start instances (+ optional etcd discovery); returns
    (cluster, pools, etcd_server) — callers own shutdown order:
    pools, then etcd, then cluster."""
    log = log or (lambda msg: print(msg, file=sys.stderr))
    cluster = LocalCluster()
    cis = []
    for port in ports:
        ci = cluster.start_instance(fixed_port=port)
        cis.append(ci)
        log(f"Listening on {ci.address}")

    pools = []
    etcd = None
    try:
        if use_etcd:
            from gubernator_tpu.cluster.etcd import EtcdPool
            from gubernator_tpu.cluster.etcdlite import EtcdLite

            etcd = EtcdLite().start()
            log(f"etcdlite on {etcd.address}")
            for ci in cis:
                pools.append(EtcdPool(
                    endpoints=[etcd.address],
                    advertise_address=ci.address,
                    on_update=ci.instance.set_peers,
                ))
            # don't print Ready until every node has watched the full
            # membership in — clients dialing at Ready must see a settled
            # ring
            want = len(cis)
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if all(ci.instance.health_check().peer_count == want
                       for ci in cis):
                    break
                time.sleep(0.02)
            else:
                raise RuntimeError("etcd membership did not converge")
        else:
            cluster.sync_peers()
    except BaseException:
        # a failed boot must not leak servers/pools/threads into the caller
        shutdown(cluster, pools, etcd)
        raise
    return cluster, pools, etcd


def shutdown(cluster, pools, etcd) -> None:
    for p in pools:
        p.close()
    if etcd is not None:
        etcd.stop()
    cluster.stop()


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser("gubernator-cluster")
    parser.add_argument(
        "--etcd", action="store_true",
        help="discover peers through an embedded etcdlite server "
             "instead of injected peer lists")
    parser.add_argument("ports", nargs="*", type=int)
    opts = parser.parse_args(sys.argv[1:] if argv is None else argv)

    cluster, pools, etcd = build_cluster(
        opts.ports or DEFAULT_PORTS, use_etcd=opts.etcd)
    print("Ready", flush=True)
    try:
        import threading

        threading.Event().wait()
    except KeyboardInterrupt:
        shutdown(cluster, pools, etcd)
    return 0


if __name__ == "__main__":
    sys.exit(main())

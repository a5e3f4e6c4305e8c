"""Client library tests against a live in-process cluster
(reference: python/tests/test_client.py:25-60)."""

import json
import urllib.error
import urllib.request

import pytest

from gubernator_tpu.client import HttpClient, V1Client, random_peer, random_string
from gubernator_tpu.cluster.harness import LocalCluster
from gubernator_tpu.service.http_gateway import HttpGateway
from gubernator_tpu.types import PeerInfo, RateLimitReq, Status


@pytest.fixture(scope="module")
def cluster():
    c = LocalCluster().start(2)
    gw = HttpGateway(c.instances[0].instance, "127.0.0.1:0")
    gw.start()
    yield c, gw
    gw.close()
    c.stop()


def test_grpc_client_dataclass_and_dict(cluster):
    c, _ = cluster
    client = V1Client(c.instances[0].address)
    r1 = client.get_rate_limits(
        [RateLimitReq(name="cl", unique_key="a", hits=1, limit=10, duration=60_000)]
    )[0]
    assert (r1.status, r1.remaining) == (Status.UNDER_LIMIT, 9)
    r2 = client.get_rate_limits(
        [{"name": "cl", "unique_key": "a", "hits": 1, "limit": 10,
          "duration": 60_000}]
    )[0]
    assert r2.remaining == 8

    hc = client.health_check()
    assert hc.status == "healthy" and hc.peer_count == 2


def test_http_client(cluster):
    c, gw = cluster
    client = HttpClient(gw.address)
    r = client.get_rate_limits(
        [RateLimitReq(name="hcl", unique_key="b", hits=1, limit=3, duration=60_000)]
    )[0]
    assert (r.status, r.remaining) == (Status.UNDER_LIMIT, 2)
    client.get_rate_limits(
        [RateLimitReq(name="hcl", unique_key="b", hits=2, limit=3, duration=60_000)]
    )
    r = client.get_rate_limits(
        [RateLimitReq(name="hcl", unique_key="b", hits=1, limit=3, duration=60_000)]
    )[0]
    assert r.status == Status.OVER_LIMIT
    assert client.health_check().status == "healthy"


def test_helpers():
    peers = [PeerInfo(address=f"h{i}") for i in range(5)]
    assert random_peer(peers) in peers
    s = random_string("ID-", 8)
    assert s.startswith("ID-") and len(s) == 11


class TestClusterBinary:
    """The gubernator-cluster binary (reference:
    cmd/gubernator-cluster/main.go; python/tests/test_client.py boots it as
    its fixture)."""

    def test_etcd_discovered_cluster(self):
        """--etcd mode: membership flows through a real EtcdPool register/
        watch lifecycle against the embedded etcdlite; cross-node requests
        must route exactly as with injected peers."""
        from gubernator_tpu.cmd.cluster_main import build_cluster, shutdown

        cluster, pools, etcd = build_cluster([0, 0, 0], use_etcd=True,
                                             log=lambda m: None)
        try:
            assert len(pools) == 3 and etcd is not None
            for ci in cluster.instances:
                assert ci.instance.health_check().peer_count == 3
            # one key, asked of every node: same counter (owner-routed)
            remaining = []
            for ci in cluster.instances:
                r = V1Client(ci.address).get_rate_limits(
                    [RateLimitReq(name="etcd_t", unique_key="k",
                                  hits=1, limit=10, duration=60_000)])[0]
                remaining.append(r.remaining)
            assert remaining == [9, 8, 7]
        finally:
            shutdown(cluster, pools, etcd)

    def test_ready_sentinel_subprocess(self):
        """`python -m ...cluster_main <port>` prints Ready and serves — the
        contract the reference's cross-language fixture depends on."""
        import os
        import subprocess
        import sys
        import threading

        from conftest import free_port

        port = free_port()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            # reuse the suite's persistent compile cache — a cold subprocess
            # otherwise recompiles every width bucket (~2 min)
            JAX_COMPILATION_CACHE_DIR=os.environ.get(
                "JAX_COMPILATION_CACHE_DIR",
                os.path.join(repo, "tests", ".jax_cache")),
            JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0.5",
        )

        def boot(p):
            log = open(f"/tmp/guber_cluster_main_{p}.log", "w")
            proc = subprocess.Popen(
                [sys.executable, "-m", "gubernator_tpu.cmd.cluster_main",
                 str(p)],
                stdout=subprocess.PIPE, stderr=log,
                text=True, env=env, cwd=repo)
            log.close()  # the child holds its own descriptor
            return proc

        proc = boot(port)
        try:
            # a wedged warmup must fail the test, not hang the whole suite;
            # a lost port-reservation race (another suite subprocess bound
            # it first — the binary then exits without Ready) retries once
            # on a fresh port
            for _attempt in range(2):
                got: list = []
                reader = threading.Thread(
                    target=lambda: got.append(proc.stdout.readline()),
                    daemon=True)
                reader.start()
                reader.join(timeout=240)
                if got and got[0].strip() == "Ready":
                    break
                if proc.poll() is None or _attempt == 1:
                    break  # alive-but-silent (or out of retries): fail below
                proc.stdout.close()  # don't leak the dead child's pipe fd
                port = free_port()
                proc = boot(port)
            assert got and got[0].strip() == "Ready", (
                got, open(f"/tmp/guber_cluster_main_{port}.log").read()[-1500:])
            r = V1Client(f"127.0.0.1:{port}").get_rate_limits(
                [RateLimitReq(name="bin_t", unique_key="k", hits=1,
                              limit=5, duration=60_000)],
                timeout=30)[0]  # first RPC may pay residual cold compiles
            assert r.remaining == 4
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()


class TestGatewayEdges:
    """HTTP gateway error surfaces (reference: gubernator.pb.gw.go's
    grpc-gateway error contract)."""

    def _url(self, cluster, path):
        _, gw = cluster
        return f"http://{gw.address}{path}"

    def test_unknown_route_404_json(self, cluster):
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(self._url(cluster, "/nope"), timeout=10)
        assert ei.value.code == 404
        body = json.load(ei.value)
        assert body["code"] == 404 and body["error"]

    def test_malformed_json_is_400_with_parseable_body(self, cluster):
        # the ParseError message embeds quoted tokens; the reply must
        # still be valid JSON
        req = urllib.request.Request(
            self._url(cluster, "/v1/GetRateLimits"),
            data=b'{"requests": [{"name": "x", "bogus_field"',
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 400
        body = json.load(ei.value)  # must not raise
        assert body["code"] == 400 and "invalid request" in body["error"]

    def test_oversized_batch_rejected(self, cluster):
        reqs = [{"name": "big", "uniqueKey": f"k{i}", "hits": "1",
                 "limit": "5", "duration": "60000"} for i in range(1001)]
        req = urllib.request.Request(
            self._url(cluster, "/v1/GetRateLimits"),
            data=json.dumps({"requests": reqs}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 400
        body = json.load(ei.value)
        assert "max size" in body["error"]

    def test_health_check_get(self, cluster):
        body = json.load(urllib.request.urlopen(
            self._url(cluster, "/v1/HealthCheck"), timeout=10))
        assert body["status"] == "healthy"
        assert int(body["peerCount"]) == 2

"""End-to-end daemon test: spawn the real daemon process with GUBER_* env,
drive it over both gRPC and the HTTP gateway (reference equivalent: the
python client fixture launching cmd/gubernator-cluster,
python/tests/test_client.py:25-39)."""

import json
import os
import urllib.request

import pytest

from conftest import free_port, spawn_daemon, stop_daemon

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def daemon():
    grpc_port, http_port = free_port(), free_port()
    proc = spawn_daemon({
        "GUBER_GRPC_ADDRESS": f"127.0.0.1:{grpc_port}",
        "GUBER_HTTP_ADDRESS": f"127.0.0.1:{http_port}",
        "GUBER_CACHE_SIZE": "4096",
        "GUBER_MIN_BATCH_WIDTH": "32",
        "GUBER_MAX_BATCH_WIDTH": "128",
        "JAX_PLATFORMS": "cpu",
    }, ready_timeout=120)
    yield {"grpc": f"127.0.0.1:{grpc_port}", "http": f"127.0.0.1:{http_port}"}
    stop_daemon(proc)


def test_grpc_roundtrip(daemon):
    from gubernator_tpu.service.grpc_api import dial_v1
    from gubernator_tpu.service.pb import gubernator_pb2 as pb

    stub = dial_v1(daemon["grpc"])
    resp = stub.GetRateLimits(
        pb.GetRateLimitsReq(
            requests=[
                pb.RateLimitReq(
                    name="rps", unique_key="k", hits=1, limit=5, duration=60_000
                )
            ]
        ),
        timeout=10,
    ).responses[0]
    assert resp.error == ""
    assert resp.remaining == 4


def test_http_gateway_roundtrip(daemon):
    body = json.dumps(
        {
            "requests": [
                {
                    "name": "rps",
                    "uniqueKey": "http-k",
                    "hits": "1",
                    "limit": "5",
                    "duration": "60000",
                }
            ]
        }
    ).encode()
    resp = urllib.request.urlopen(
        urllib.request.Request(
            f"http://{daemon['http']}/v1/GetRateLimits",
            data=body,
            headers={"Content-Type": "application/json"},
        ),
        timeout=10,
    )
    data = json.loads(resp.read())
    assert data["responses"][0]["remaining"] == "4"


def test_http_health_and_metrics(daemon):
    health = json.loads(
        urllib.request.urlopen(
            f"http://{daemon['http']}/v1/HealthCheck", timeout=10
        ).read()
    )
    assert health["status"] == "healthy"
    metrics = urllib.request.urlopen(
        f"http://{daemon['http']}/metrics", timeout=10
    ).read().decode()
    assert "grpc_request_duration_milliseconds" in metrics
    assert "engine_decisions_total" in metrics
    # stage clocks exposed once traffic has flowed
    assert 'engine_stage_seconds_total{stage="device"}' in metrics


def test_profile_env_parsing(monkeypatch):
    from gubernator_tpu.cmd.envconf import config_from_env

    monkeypatch.setenv("GUBER_PROFILE_PORT", "9999")
    monkeypatch.setenv("GUBER_PROFILE_DIR", "/tmp/xla-trace")
    conf = config_from_env([])
    assert conf.profile_port == 9999
    assert conf.profile_dir == "/tmp/xla-trace"


def test_start_profiling_noop_by_default():
    from gubernator_tpu.cmd.daemon import start_profiling
    from gubernator_tpu.cmd.envconf import DaemonConfig

    assert start_profiling(DaemonConfig()) is False


def test_collectives_env_parsing(monkeypatch):
    from gubernator_tpu.cmd.envconf import config_from_env

    monkeypatch.setenv("GUBER_COLLECTIVES", "psum")
    assert config_from_env([]).collectives == "psum"
    monkeypatch.delenv("GUBER_COLLECTIVES")
    assert config_from_env([]).collectives == "psum"


def test_collectives_env_validation(monkeypatch):
    import pytest

    from gubernator_tpu.cmd.envconf import config_from_env

    # "ring" named the Pallas ring all-reduce the TPU compiler refused
    for bad in ("rings", "ring"):
        monkeypatch.setenv("GUBER_COLLECTIVES", bad)
        with pytest.raises(ValueError, match="GUBER_COLLECTIVES"):
            config_from_env([])


def test_etcd_env_parsing(monkeypatch):
    """Full GUBER_ETCD_* surface (reference: config.go:118-123,203-260)."""
    from gubernator_tpu.cmd.envconf import config_from_env

    monkeypatch.setenv("GUBER_ETCD_ENDPOINTS", "e1:2379,e2:2379")
    monkeypatch.setenv("GUBER_ETCD_ADVERTISE_ADDRESS", "10.1.1.1:81")
    monkeypatch.setenv("GUBER_ETCD_KEY_PREFIX", "/my-peers")
    monkeypatch.setenv("GUBER_ETCD_DIAL_TIMEOUT", "2s")
    monkeypatch.setenv("GUBER_ETCD_USER", "guber")
    monkeypatch.setenv("GUBER_ETCD_PASSWORD", "s3cret")
    conf = config_from_env([])
    assert conf.etcd_endpoints == ["e1:2379", "e2:2379"]
    assert conf.etcd_advertise_address == "10.1.1.1:81"
    assert conf.etcd_key_prefix == "/my-peers"
    assert conf.etcd_dial_timeout_s == 2.0
    assert conf.etcd_user == "guber"
    assert conf.etcd_password == "s3cret"
    assert not conf.etcd_tls_enable  # no GUBER_ETCD_TLS_* set
    monkeypatch.setenv("GUBER_ETCD_TLS_CA", "/certs/ca.pem")
    monkeypatch.setenv("GUBER_ETCD_TLS_SKIP_VERIFY", "true")
    conf = config_from_env([])
    assert conf.etcd_tls_enable
    assert conf.etcd_tls_ca == "/certs/ca.pem"
    assert conf.etcd_tls_skip_verify


def test_memberlist_advertise_port(monkeypatch):
    from gubernator_tpu.cmd.envconf import config_from_env

    monkeypatch.setenv("GUBER_MEMBERLIST_ADVERTISE_ADDRESS", "10.0.0.5")
    monkeypatch.setenv("GUBER_MEMBERLIST_ADVERTISE_PORT", "7777")
    conf = config_from_env([])
    assert conf.gossip_bind == "10.0.0.5"
    assert conf.gossip_advertise_port == 7777


def test_memberlist_secret_keys_build_the_keyring(monkeypatch):
    """GUBER_MEMBERLIST_SECRET_KEYS (base64, primary first) must reach
    the pool as a decoded keyring; bad base64 or a wrong-length key must
    fail the boot loudly, not produce a silently-plaintext fleet."""
    import base64

    import pytest as _pytest

    from gubernator_tpu.cmd.daemon import build_pool
    from gubernator_tpu.cmd.envconf import config_from_env

    primary = base64.b64encode(b"p" * 32).decode()
    old = base64.b64encode(b"o" * 16).decode()
    monkeypatch.setenv("GUBER_MEMBERLIST_ADVERTISE_ADDRESS", "127.0.0.1")
    monkeypatch.setenv("GUBER_MEMBERLIST_ADVERTISE_PORT", "0")
    monkeypatch.setenv("GUBER_MEMBERLIST_SECRET_KEYS",
                       f"{primary},{old}")
    conf = config_from_env([])
    assert conf.memberlist_secret_keys == [primary, old]

    class _Inst:
        advertise_address = "127.0.0.1:9081"

        def set_peers(self, peers):
            pass

    pool = build_pool(conf, _Inst())
    try:
        assert pool is not None
        assert pool._keyring == [b"p" * 32, b"o" * 16]
        assert pool._primary_key == b"p" * 32
    finally:
        pool.close()

    # a wrong-length key must refuse the boot
    monkeypatch.setenv("GUBER_MEMBERLIST_SECRET_KEYS",
                       base64.b64encode(b"short").decode())
    with _pytest.raises(ValueError):
        build_pool(config_from_env([]), _Inst())


def test_skip_verify_false_is_false(monkeypatch):
    """GUBER_ETCD_TLS_SKIP_VERIFY=false must not enable pinning (the
    reference treats any non-empty value as true, config.go:254 — we parse
    it properly; PARITY.md #13)."""
    from gubernator_tpu.cmd.envconf import config_from_env

    monkeypatch.setenv("GUBER_ETCD_TLS_SKIP_VERIFY", "false")
    conf = config_from_env([])
    assert conf.etcd_tls_enable  # any GUBER_ETCD_TLS_* enables TLS
    assert not conf.etcd_tls_skip_verify
    monkeypatch.setenv("GUBER_ETCD_TLS_SKIP_VERIFY", "maybe")
    import pytest as _pytest
    with _pytest.raises(ValueError):
        config_from_env([])


def test_sharded_backend_daemon():
    """GUBER_BACKEND=sharded over the 8-virtual-device CPU mesh: the daemon
    must warm the mesh kernels, serve plain and GLOBAL traffic (the host
    tier owns GLOBAL in daemon mode), and expose engine metrics including
    the sharded backend's standalone GLOBAL counters."""
    import re
    import urllib.request

    grpc_port, http_port = free_port(), free_port()
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    proc = spawn_daemon({
        "GUBER_GRPC_ADDRESS": f"127.0.0.1:{grpc_port}",
        "GUBER_HTTP_ADDRESS": f"127.0.0.1:{http_port}",
        "GUBER_BACKEND": "sharded",
        "GUBER_CACHE_SIZE": "4096",
        "GUBER_MIN_BATCH_WIDTH": "8",
        "GUBER_MAX_BATCH_WIDTH": "32",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS":
            f"{flags} --xla_force_host_platform_device_count=8".strip(),
    })
    try:
        from gubernator_tpu.service.grpc_api import dial_v1
        from gubernator_tpu.service.pb import gubernator_pb2 as pb

        stub = dial_v1(f"127.0.0.1:{grpc_port}")
        mk = lambda k, h, b=0: pb.RateLimitReq(
            name="sd", unique_key=k, hits=h, limit=100, duration=3_600_000,
            behavior=b)
        # plain traffic over the mesh (keys spread across 8 shards)
        resp = stub.GetRateLimits(pb.GetRateLimitsReq(
            requests=[mk(f"k{i}", 1) for i in range(16)]), timeout=30)
        assert all(r.error == "" and r.remaining == 99
                   for r in resp.responses)
        # GLOBAL behavior in a daemon rides the HOST tier (the instance
        # strips the GLOBAL bit before the backend; the engine-level
        # mirror/psum tier is the standalone-library path, tested over the
        # mesh in tests/test_parallel.py). A single-node daemon owns every
        # key, so GLOBAL requests process authoritatively and sequentially.
        r1 = stub.GetRateLimits(pb.GetRateLimitsReq(
            requests=[mk("g", 5, 2)]), timeout=30).responses[0]
        assert r1.remaining == 95
        r2 = stub.GetRateLimits(pb.GetRateLimitsReq(
            requests=[mk("g", 1, 2)]), timeout=30).responses[0]
        assert r2.error == "" and r2.remaining == 94
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{http_port}/metrics",
            timeout=10).read().decode()
        assert "engine_decisions_total" in text
        assert 'engine_stage_seconds_total{stage="device"}' in text
        # the sharded backend's standalone GLOBAL counters are exposed
        # (zero here: the host tier owns GLOBAL in daemon mode)
        assert "engine_global_syncs_total" in text
    finally:
        stop_daemon(proc)


def test_load_generator_cli():
    """The gubernator-cli load generator (reference:
    cmd/gubernator-cli/main.go:42-85) drives a live cluster in-process: a
    bounded run must push traffic, observe OVER_LIMIT on drained limits,
    and report a throughput line."""
    import io
    from contextlib import redirect_stdout

    from gubernator_tpu.cluster.harness import LocalCluster
    from gubernator_tpu.cmd import cli

    import random as _random

    c = LocalCluster().start(1)
    try:
        # deterministic workload: seed guarantees low-limit keys exist, so
        # OVER_LIMIT is reachable regardless of machine speed
        _random.seed(7)
        out = io.StringIO()
        with redirect_stdout(out):
            rc = cli.main([c.instances[0].address, "--seconds", "2",
                           "--concurrency", "4", "--requests", "20"])
        assert rc == 0
        summary = out.getvalue().strip().splitlines()[-1]
        assert summary.startswith("sent=")
        fields = dict(f.split("=") for f in summary.split())
        assert int(fields["sent"]) > 20
        assert int(fields["errors"]) == 0
        # 20 keys hammered for 2s, lowest limit small under seed 7: some
        # must go over
        assert int(fields["over_limit"]) > 0
    finally:
        c.stop()

"""Client library: gRPC and HTTP clients for any gubernator-compatible server.

Role parity with the reference's client helpers and python package
(reference: client.go:33-79, python/gubernator/__init__.py:19-21) — since
this framework is Python, the "python client" is first-class here rather
than a generated-stub wrapper.
"""

from __future__ import annotations

import json
import random
import string
import urllib.request
from typing import List, Optional, Sequence, Union

from gubernator_tpu.service.convert import req_to_pb, resp_from_pb
from gubernator_tpu.service.grpc_api import V1Stub, dial_v1
from gubernator_tpu.service.pb import gubernator_pb2 as pb
from gubernator_tpu.types import (
    HealthCheckResp,
    PeerInfo,
    RateLimitReq,
    RateLimitResp,
)

ReqLike = Union[RateLimitReq, "pb.RateLimitReq", dict]


def _coerce(req: ReqLike) -> "pb.RateLimitReq":
    if isinstance(req, pb.RateLimitReq):
        return req
    if isinstance(req, RateLimitReq):
        return req_to_pb(req)
    if isinstance(req, dict):
        return pb.RateLimitReq(**req)
    raise TypeError(f"cannot convert {type(req)} to RateLimitReq")


class V1Client:
    """gRPC client (reference: client.go:38-49 DialV1Server)."""

    def __init__(self, address: str, stub: Optional[V1Stub] = None):
        self.address = address
        self._stub = stub or dial_v1(address)

    def get_rate_limits(
        self, requests: Sequence[ReqLike], timeout: float = 5.0
    ) -> List[RateLimitResp]:
        resp = self._stub.GetRateLimits(
            pb.GetRateLimitsReq(requests=[_coerce(r) for r in requests]),
            timeout=timeout,
        )
        return [resp_from_pb(m) for m in resp.responses]

    def health_check(self, timeout: float = 5.0) -> HealthCheckResp:
        h = self._stub.HealthCheck(pb.HealthCheckReq(), timeout=timeout)
        return HealthCheckResp(
            status=h.status, message=h.message, peer_count=h.peer_count
        )


class HttpClient:
    """Zero-dependency JSON client for the HTTP gateway
    (reference: python/gubernator using the grpc-gateway routes)."""

    def __init__(self, address: str):
        self.base = address if address.startswith("http") else f"http://{address}"

    def get_rate_limits(
        self, requests: Sequence[ReqLike], timeout: float = 5.0
    ) -> List[RateLimitResp]:
        body = json.dumps(
            {
                "requests": [
                    {
                        "name": m.name,
                        "uniqueKey": m.unique_key,
                        "hits": str(m.hits),
                        "limit": str(m.limit),
                        "duration": str(m.duration),
                        "algorithm": int(m.algorithm),
                        "behavior": int(m.behavior),
                    }
                    for m in map(_coerce, requests)
                ]
            }
        ).encode()
        raw = urllib.request.urlopen(
            urllib.request.Request(
                f"{self.base}/v1/GetRateLimits",
                data=body,
                headers={"Content-Type": "application/json"},
            ),
            timeout=timeout,
        ).read()
        out = []
        for r in json.loads(raw).get("responses", []):
            out.append(
                RateLimitResp(
                    status=1 if r.get("status") == "OVER_LIMIT" else 0,
                    limit=int(r.get("limit", 0)),
                    remaining=int(r.get("remaining", 0)),
                    reset_time=int(r.get("resetTime", 0)),
                    error=r.get("error", ""),
                    metadata=r.get("metadata", {}),
                )
            )
        return out

    def health_check(self, timeout: float = 5.0) -> HealthCheckResp:
        raw = urllib.request.urlopen(
            f"{self.base}/v1/HealthCheck", timeout=timeout
        ).read()
        h = json.loads(raw)
        return HealthCheckResp(
            status=h.get("status", ""),
            message=h.get("message", ""),
            peer_count=int(h.get("peerCount", 0)),
        )


class LinkClient:
    """Framework-native public client: the columnar peerlink transport for
    the PUBLIC surface (method 0 — full router semantics server-side),
    with transparent per-call fallback to the wire-compatible gRPC tier.

    The public gRPC surface stays untouched for reference-ecosystem
    clients; this client exists because Python gRPC pays per-RPC
    GIL-held machinery while the link's columnar frames (and, for lone
    requests on a standalone node, the server's C++ IO-thread decision
    path) serve the same contract without it. Negotiation mirrors the
    peer tier:
    the link listens at grpc_port + GUBER_PEER_LINK_OFFSET (default
    1000); servers that don't answer it get gRPC."""

    def __init__(self, address: str, link_offset: int = 1000,
                 connect_timeout_s: float = 1.0):
        from gubernator_tpu.service.peerlink import PeerLinkClient

        self.address = address
        host, _, port = address.rpartition(":")
        self._link = None
        self._grpc: Optional[V1Client] = None
        try:
            self._link = PeerLinkClient(
                f"{host or '127.0.0.1'}:{int(port) + link_offset}",
                connect_timeout_s=connect_timeout_s)
        except OSError:
            pass  # server predates the link / link disabled: gRPC only

    def get_rate_limits(
        self, requests: Sequence[RateLimitReq], timeout: float = 5.0
    ) -> List[RateLimitResp]:
        from gubernator_tpu.service.peerlink import (
            METHOD_GET_RATE_LIMITS,
            PeerLinkTimeout,
            PeerLinkUnencodable,
        )
        from gubernator_tpu.service.peerlink import (
            PeerLinkError as _LinkErr,
        )

        if self._link is not None:
            try:
                return self._link.call(
                    METHOD_GET_RATE_LIMITS, list(requests), timeout)
            except PeerLinkUnencodable:
                pass  # this call can't ride the frames: gRPC below
            except PeerLinkTimeout:
                raise  # delivery-uncertain: surface it like a deadline
            except _LinkErr:
                self._link.close()  # free the fd + reader thread
                self._link = None  # broken link: stay on gRPC
        return self._grpc_client().get_rate_limits(requests, timeout)

    def health_check(self, timeout: float = 5.0) -> HealthCheckResp:
        return self._grpc_client().health_check(timeout)

    def close(self) -> None:
        if self._link is not None:
            self._link.close()
            self._link = None

    def _grpc_client(self) -> V1Client:
        if self._grpc is None:
            self._grpc = V1Client(self.address)
        return self._grpc


def random_peer(peers: Sequence[PeerInfo]) -> PeerInfo:
    """(reference: client.go:68-71)"""
    return random.choice(list(peers))


def random_string(prefix: str = "", n: int = 10) -> str:
    """(reference: client.go:74-79)"""
    return prefix + "".join(
        random.choices(string.ascii_letters + string.digits, k=n)
    )


def to_timestamp_ms(seconds: float) -> int:
    """Seconds -> unix ms (reference: client.go:57-60 ToTimeStamp)."""
    return int(seconds * 1000)

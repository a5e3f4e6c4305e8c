"""The benchmark's own protobuf codec for `pb.gubernator.V1/GetRateLimits`
(proto/gubernator.proto), so that load-generator processes import nothing
of the program — and no JAX.

Requests are built once per key as bytes and joined into call bodies;
responses are kept as bytes inside the measured window and decoded after it.
"""

from __future__ import annotations

from typing import List, Tuple

GRPC_METHOD = "/pb.gubernator.V1/GetRateLimits"


def _varint(n: int) -> bytes:
    n &= 0xFFFFFFFFFFFFFFFF  # negative int64 as ten bytes, as protobuf does
    out = bytearray()
    while n > 0x7F:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def encode_request(name: bytes, unique_key: bytes, hits: int, limit: int,
                   duration: int, algorithm: int, behavior: int) -> bytes:
    """One `requests` entry of GetRateLimitsReq (field 1, length-delimited),
    proto3: zero fields are left out."""
    body = bytearray()
    for tag, raw in ((0x0A, name), (0x12, unique_key)):
        if raw:
            body += bytes([tag]) + _varint(len(raw)) + raw
    for tag, n in ((0x18, hits), (0x20, limit), (0x28, duration),
                   (0x30, algorithm), (0x38, behavior)):
        if n:
            body += bytes([tag]) + _varint(n)
    return b"\x0a" + _varint(len(body)) + bytes(body)


def decode_responses(buf: bytes) -> List[Tuple[int, int, int, int, str]]:
    """GetRateLimitsResp -> [(status, limit, remaining, reset_time, error)]
    in order. Metadata (field 6) is skipped. Raises ValueError on bytes
    that are not such a message."""
    out = []
    i, n = 0, len(buf)
    while i < n:
        if buf[i] != 0x0A:
            raise ValueError(f"unexpected tag {buf[i]:#x} at {i}")
        ln, i = _read_varint(buf, i + 1)
        end = i + ln
        if end > n:
            raise ValueError("truncated response entry")
        vals = [0, 0, 0, 0]
        error = ""
        while i < end:
            tag = buf[i]
            field, kind = tag >> 3, tag & 7
            if kind == 0:
                v, i = _read_varint(buf, i + 1)
                if 1 <= field <= 4:
                    if v >= 1 << 63:
                        v -= 1 << 64
                    vals[field - 1] = v
            elif kind == 2:
                ln2, i = _read_varint(buf, i + 1)
                if field == 5:
                    error = buf[i:i + ln2].decode("utf-8", "replace")
                i += ln2
            else:
                raise ValueError(f"unexpected wire type {kind} at {i}")
        if i != end:
            raise ValueError("response entry overran its length")
        out.append((vals[0], vals[1], vals[2], vals[3], error))
    return out


def _read_varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = v = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7

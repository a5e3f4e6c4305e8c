"""The native serving shim's Python half: peerlink server + client.

The reference's peer hop is a ~30 µs Go gRPC unary call (reference:
README.md:104, peer_client.go:127-140); Python gRPC pays ~0.4 ms per RPC in
GIL-held machinery. peerlink moves everything per-RPC into C++
(native/peerlink.cpp: epoll IO, frame parse, adaptive micro-batch
aggregation) and enters Python once per BATCH:

    worker loop:  pls_next_batch (blocks in C, GIL released)
                  -> wire columns straight to the engine (columnar), or
                     RateLimitReqs through the Instance handler
                  -> pls_send_partial per finished row-span (C++
                     serializes + writes: partial frames to a v2 peer,
                     one whole frame per request to anyone else)

Two methods ride the same frames: GetPeerRateLimits (method 1, the peer
hop — owner-apply semantics) and GetRateLimits (method 0, the lean public
surface with full router semantics). The public gRPC+HTTP surface remains
wire-compatible with the reference and untouched; peerlink is the
framework-internal fast path, negotiated by port convention
(peer grpc port + GUBER_PEER_LINK_OFFSET) with transparent fallback to
gRPC when the peer doesn't answer it.
"""

from __future__ import annotations

import collections
import ctypes
import logging
import socket
import struct
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeout
from typing import Dict, List, Optional, Sequence

import numpy as np

from gubernator_tpu.obs import witness
from gubernator_tpu.service import faults
from gubernator_tpu.types import (
    MAX_BATCH_SIZE,
    SLOW_PATH_BEHAVIOR_MASK as _COLUMNAR_SLOW_MASK,
    RateLimitReq,
    RateLimitResp,
)

log = logging.getLogger("gubernator_tpu.peerlink")

METHOD_GET_RATE_LIMITS = 0
METHOD_GET_PEER_RATE_LIMITS = 1
# Method-byte flag: the frame's FIRST item is a trace-context carrier (its
# unique_key field holds the W3C traceparent; its response lane is a zero
# placeholder). The reserved high bits of the method byte are the frame
# format's only spare field, so trace context rides there without touching
# the C++ parser: flagged methods never match the IO-thread fast paths
# (they check method == 0/1 exactly) and reach the Python workers with the
# flag intact.
METHOD_TRACED = 0x80
TRACE_CARRIER_NAME = "tp"
# Second reserved method-byte flag: the frame carries a deadline-budget
# carrier item (its unique_key holds the remaining hop budget in ms as a
# decimal string — service/deadline.py). Same no-C++-change trick as
# METHOD_TRACED: flagged methods never match the IO-thread fast paths, so
# the carrier reaches the Python workers intact. Carrier order when both
# flags are set: trace first, deadline second.
METHOD_DEADLINE = 0x40
# Third reserved method-byte flag: the frame carries a hot-key lease ask
# (service/leases.py — its unique_key holds the hash key the sender wants
# a lease for). Same no-C++-change trick again; the peerlink response
# format has no metadata column on the Python side, so the owner's grant
# rides back IN the carrier's own response lane (_fill_lease_lane):
# status = frame-relative index of the granted item (-1 = no grant),
# limit = budget, remaining = ttl_ms, reset = seq. Carrier order when
# several flags are set: trace, deadline, lease.
METHOD_LEASE = 0x20
METHOD_FLAGS = METHOD_TRACED | METHOD_DEADLINE | METHOD_LEASE
DEADLINE_CARRIER_NAME = "dl"
LEASE_CARRIER_NAME = "ls"


def trace_carrier(span) -> RateLimitReq:
    """The reserved item 0 of a TRACED frame (see METHOD_TRACED)."""
    from gubernator_tpu.obs.trace import format_traceparent

    return RateLimitReq(name=TRACE_CARRIER_NAME,
                        unique_key=format_traceparent(span))


def deadline_carrier(budget_ms: float) -> RateLimitReq:
    """The reserved carrier item of a DEADLINE frame (see
    METHOD_DEADLINE): the budget this hop was granted, already
    decremented by the sender's elapsed time."""
    return RateLimitReq(name=DEADLINE_CARRIER_NAME,
                        unique_key=f"{budget_ms:.3f}")


def lease_carrier(hash_key: str) -> RateLimitReq:
    """The reserved carrier item of a LEASE frame (see METHOD_LEASE):
    the hash key this sender wants a hot-key lease for. Its response
    lane carries the owner's grant instead of a zero placeholder."""
    return RateLimitReq(name=LEASE_CARRIER_NAME, unique_key=hash_key)


# Columnar wire layout (see native/peerlink.cpp): fields ride as arrays,
# encoded/decoded with numpy bulk ops — per-item marshalling cost is what
# made the gRPC tier slow, so the frames avoid it on both ends.
_ONE_HDR = struct.Struct("<QBHHH")  # rid, method, count=1, name_len, ukey_len
_ONE_FIX = struct.Struct("<qqqII")  # hits, limit, duration, algo, behavior




def _pb_varint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _encode_pb_metadata(md: Dict[str, str]) -> bytes:
    """RateLimitResp.metadata (field 6 map<string,string>) as raw proto
    bytes — the C++ gRPC front embeds them verbatim into the response
    item, so routed/GLOBAL replies keep their owner metadata on the
    wire-compatible surface (proto/gubernator.proto:67)."""
    out = bytearray()
    for k, v in md.items():
        kb, vb = k.encode(), str(v).encode()
        entry = (b"\x0a" + _pb_varint(len(kb)) + kb
                 + b"\x12" + _pb_varint(len(vb)) + vb)
        out += b"\x32" + _pb_varint(len(entry)) + entry
    return bytes(out)


class _RawAbort(Exception):
    """context.abort() surfaced from a servicer on the raw gRPC-front
    path; becomes a trailers-only grpc-status reply."""

    def __init__(self, code: int, details: str):
        super().__init__(details)
        self.code = code
        self.details = details


class _RawCtx:
    """Minimal grpc.ServicerContext stand-in for the raw-punt path: the
    servicers only call abort()."""

    @staticmethod
    def abort(code, details: str = ""):
        num = code.value[0] if hasattr(code, "value") else int(code)
        raise _RawAbort(num, details)


class PeerLinkError(RuntimeError):
    """Transport-level failure: the link is broken — callers drop it and
    fall back to the gRPC tier for a while."""


class PeerLinkTimeout(PeerLinkError):
    """No response in time. The frame MAY already be applying at the peer,
    so callers must NOT re-send (double-counted hits) — surface the error,
    exactly as a gRPC deadline does."""


class PeerLinkUnencodable(PeerLinkError):
    """This request cannot ride the wire format (oversized key, too many
    items). The link itself is healthy: route just this call over gRPC."""


# per-field wire bound (server closes the conn on anything bigger); the
# gRPC tier has no such cap, so oversized keys fall back there
MAX_FIELD_BYTES = 1024
MAX_FRAME_ITEMS = 1024

# ---- wire contract v2 (docs/wire.md) ----
# Reserved control-method range: real methods occupy 0x00..0xE1 (method |
# carrier flags), so 0xF0..0xFF can carry control frames both ends of a
# MIXED-version link tolerate: the GREETING is shaped as a valid v1 reply
# frame with rid 0 (client rids start at 1 — a v1 client parses it and
# drops the unknown rid), and the HELLO is only ever sent in answer to a
# GREETING, so it never reaches a v1 server.
WIRE_GREETING = 0xF0  # server -> client on accept: "I can speak v2"
WIRE_HELLO = 0xF1     # client -> server: upgrade this conn to v2
WIRE_PARTIAL = 0xF2   # server -> client: seq-numbered partial reply

_PARTIAL_HDR = struct.Struct("<QBHHHB")  # rid, 0xF2, count, seq, base, final


def encode_request_frame(rid: int, method: int,
                         reqs: Sequence[RateLimitReq]) -> bytes:
    """Columnar encode. Raises PeerLinkError for anything the wire format
    cannot carry — callers route those requests over gRPC instead."""
    n = len(reqs)
    if not 0 < n <= MAX_FRAME_ITEMS:
        raise PeerLinkUnencodable(
            f"frame must carry 1..{MAX_FRAME_ITEMS} requests")
    if n == 1:
        # the lone peer-hop path: two packs, zero numpy
        r = reqs[0]
        name = r.name.encode()
        ukey = r.unique_key.encode()
        if len(name) > MAX_FIELD_BYTES or len(ukey) > MAX_FIELD_BYTES:
            raise PeerLinkUnencodable("key too long for peerlink")
        body = (_ONE_HDR.pack(rid, method, 1, len(name), len(ukey))
                + name + ukey
                + _ONE_FIX.pack(r.hits, r.limit, r.duration,
                                int(r.algorithm), int(r.behavior)))
        return struct.pack("<I", len(body)) + body
    if n <= 4:
        # numpy's fixed setup costs more than it saves on tiny frames (the
        # lone peer-hop path is all tiny frames)
        parts = [struct.pack("<QBH", rid, method, n)]
        names = [r.name.encode() for r in reqs]
        ukeys = [r.unique_key.encode() for r in reqs]
        for a, b in zip(names, ukeys):
            if len(a) > MAX_FIELD_BYTES or len(b) > MAX_FIELD_BYTES:
                raise PeerLinkUnencodable("key too long for peerlink")
        parts.append(struct.pack(f"<{n}H", *(len(a) for a in names)))
        parts.append(struct.pack(f"<{n}H", *(len(b) for b in ukeys)))
        parts.extend(a + b for a, b in zip(names, ukeys))
        for col in ("hits", "limit", "duration"):
            parts.append(struct.pack(
                f"<{n}q", *(getattr(r, col) for r in reqs)))
        parts.append(struct.pack(f"<{n}I", *(int(r.algorithm) for r in reqs)))
        parts.append(struct.pack(f"<{n}I", *(int(r.behavior) for r in reqs)))
        body = b"".join(parts)
        return struct.pack("<I", len(body)) + body
    names = [r.name.encode() for r in reqs]
    ukeys = [r.unique_key.encode() for r in reqs]
    nl = [len(b) for b in names]
    ul = [len(b) for b in ukeys]
    # bound-check BEFORE the uint16 casts: an oversized length would raise
    # OverflowError (numpy 2) or silently wrap (numpy 1), not fall back
    if max(nl) > MAX_FIELD_BYTES or max(ul) > MAX_FIELD_BYTES:
        raise PeerLinkUnencodable("key too long for peerlink")
    name_len = np.array(nl, np.uint16)
    ukey_len = np.array(ul, np.uint16)
    keys = b"".join(a + b for a, b in zip(names, ukeys))
    cols = np.empty((3, n), np.int64)
    meta = np.empty((2, n), np.uint32)
    for j, r in enumerate(reqs):  # one pass builds every column
        cols[0, j] = r.hits
        cols[1, j] = r.limit
        cols[2, j] = r.duration
        meta[0, j] = int(r.algorithm)
        meta[1, j] = int(r.behavior)
    body = b"".join((
        struct.pack("<QBH", rid, method, n),
        name_len.tobytes(), ukey_len.tobytes(), keys,
        cols.tobytes(), meta.tobytes(),
    ))
    return struct.pack("<I", len(body)) + body


def decode_response_frame(payload: memoryview) -> List[RateLimitResp]:
    _rid, _method, count = struct.unpack_from("<QBH", payload, 0)
    return _decode_resp_items(payload, count, 11)


def decode_partial_frame(payload: memoryview):
    """Decode one v2 0xF2 partial reply frame (header layout documented
    at WIRE_PARTIAL / docs/wire.md): (rid, seq, base, final, resps)."""
    rid, _m, count, seq, base, fin = _PARTIAL_HDR.unpack_from(payload, 0)
    return rid, seq, base, bool(fin), _decode_resp_items(payload, count, 16)


def encode_reshard_frame(rid: int, seq: int, count: int, final: bool,
                         payload: bytes) -> bytes:
    """Reshard bulk-transfer frames reuse the v2 partial-frame header
    verbatim (rid = transfer id, count = rows in this chunk, seq-numbered,
    final-flagged) so the handoff stream inherits the same
    sequencing/termination contract as a streamed response — but they
    travel inside the raw Debug RPC body (service/reshard.py), never on a
    serving link, so v1-only peers take them too."""
    return _PARTIAL_HDR.pack(rid, WIRE_PARTIAL, count, seq, seq,
                             1 if final else 0) + payload


def decode_reshard_frame(buf):
    """Inverse of encode_reshard_frame: (rid, seq, count, final, payload)."""
    rid, method, count, seq, _base, fin = _PARTIAL_HDR.unpack_from(buf, 0)
    if method != WIRE_PARTIAL:
        raise PeerLinkError(f"not a reshard frame (method {method:#x})")
    return rid, seq, count, bool(fin), bytes(buf[_PARTIAL_HDR.size:])


def _decode_resp_items(payload: memoryview, count: int,
                       off: int) -> List[RateLimitResp]:
    """The response columns shared by the v1 whole frame and the v2
    partial frame — same layout, different header length."""
    if count <= 4:  # mirror the tiny-frame encode fast path
        st = struct.unpack_from(f"<{count}i", payload, off)
        off += 4 * count
        li = struct.unpack_from(f"<{count}q", payload, off)
        off += 8 * count
        re = struct.unpack_from(f"<{count}q", payload, off)
        off += 8 * count
        rs = struct.unpack_from(f"<{count}q", payload, off)
        off += 8 * count
        el = struct.unpack_from(f"<{count}H", payload, off)
        off += 2 * count
        out = []
        for i in range(count):
            err = (bytes(payload[off:off + el[i]]).decode()
                   if el[i] else "")
            off += el[i]
            out.append(RateLimitResp(status=st[i], limit=li[i],
                                     remaining=re[i], reset_time=rs[i],
                                     error=err))
        return out
    status = np.frombuffer(payload, np.int32, count, off)
    off += 4 * count
    limit = np.frombuffer(payload, np.int64, count, off)
    off += 8 * count
    remaining = np.frombuffer(payload, np.int64, count, off)
    off += 8 * count
    reset = np.frombuffer(payload, np.int64, count, off)
    off += 8 * count
    err_len = np.frombuffer(payload, np.uint16, count, off)
    off += 2 * count
    st, li, re, rs = (status.tolist(), limit.tolist(), remaining.tolist(),
                      reset.tolist())
    if not err_len.any():  # the common, error-free fast path
        return [RateLimitResp(status=st[i], limit=li[i], remaining=re[i],
                              reset_time=rs[i]) for i in range(count)]
    out = []
    for i in range(count):
        elen = int(err_len[i])
        err = bytes(payload[off:off + elen]).decode() if elen else ""
        off += elen
        out.append(RateLimitResp(status=st[i], limit=li[i], remaining=re[i],
                                 reset_time=rs[i], error=err))
    return out


class PeerLinkClient:
    """One persistent framed connection: writers interleave under a lock,
    a reader thread demuxes responses by rid into futures."""

    def __init__(self, address: str, connect_timeout_s: float = 1.0,
                 fault_key: str = "", wire_v2: bool = True,
                 recorder=None):
        host, _, port = address.rpartition(":")
        self.address = address
        self._recorder = recorder  # flight recorder (obs/events.py) or None
        # the fault-injection identity of this link (faults.py): PeerClient
        # passes the peer's ADVERTISED address so one GUBER_FAULT_SPEC peer
        # key covers both transports; standalone clients default to the
        # link address itself
        self._fault_key = fault_key or address
        self._sock = socket.create_connection(
            (host or "127.0.0.1", int(port)), timeout=connect_timeout_s)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._sock.settimeout(None)
        self._wlock = witness.make_lock("peerlink.write")
        self._futures: Dict[int, Future] = {}
        self._flock = witness.make_lock("peerlink.frames")
        self._rid = 0
        self._closed = False
        # wire contract v2: stay at v1 until the server's GREETING proves
        # it streams partial replies; the HELLO upgrade goes out from the
        # reader thread. Reassembly state (guarded by _flock) must never
        # outlive its future — call(), _fail and whole-frame arrival all
        # clear it, so a dead rid cannot leak rows. wire_v2=False is the
        # interop tests' old binary (a client that ignores the greeting);
        # no production caller passes it.
        self._want_v2 = bool(wire_v2)
        self.wire_version = 1
        self._expected: Dict[int, int] = {}  # rid -> response count due
        self._partial: Dict[int, list] = {}  # rid -> [rows, next_seq]
        self._reader = threading.Thread(
            target=self._read_loop, name=f"peerlink-read-{address}",
            daemon=True)
        self._reader.start()

    def call(self, method: int, reqs: Sequence[RateLimitReq],
             timeout_s: float) -> List[RateLimitResp]:
        if not reqs:
            return []
        fut, rid = self.call_async(method, reqs)
        try:
            return fut.result(timeout=timeout_s)
        except FutureTimeout:
            with self._flock:
                self._futures.pop(rid, None)
                self._expected.pop(rid, None)
                self._partial.pop(rid, None)
            raise PeerLinkTimeout("peerlink response timeout") from None
        except PeerLinkError as e:
            # the frame was already delivered to the socket when the link
            # died: delivery is UNCERTAIN, so this must surface like a
            # timeout (re-sending could double-apply), not like a pre-send
            # transport error
            raise PeerLinkTimeout(
                f"link failed awaiting response: {e}") from e

    def call_async(self, method: int, reqs: Sequence[RateLimitReq]):
        """Fire one frame; returns (future, rid). The future resolves to
        the response list (pipelined callers keep several in flight)."""
        if self._closed:
            raise PeerLinkError("link closed")
        if faults.active() is not None:
            # the fault-injection choke point for the peerlink transport,
            # translated into this wire's failure taxonomy: 'error' is a
            # pre-send link break (callers fall back to gRPC), 'timeout'/
            # 'drop' surface as delivery-uncertain PeerLinkTimeout
            try:
                faults.on_call(self._fault_key, "peerlink")
            except faults.FaultError as e:
                raise PeerLinkError(str(e)) from e
            except faults.FaultTimeout as e:
                raise PeerLinkTimeout(str(e)) from e
        # encode BEFORE registering: an unencodable request must not leak
        # a future that nobody will ever complete
        with self._flock:
            self._rid += 1
            rid = self._rid
        frame = encode_request_frame(rid, method, reqs)
        fut: Future = Future()
        with self._flock:
            self._futures[rid] = fut
            self._expected[rid] = len(reqs)
        try:
            with self._wlock:
                self._sock.sendall(frame)
        except OSError as e:
            self._fail(e)
            raise PeerLinkError(str(e)) from e
        return fut, rid

    def close(self) -> None:
        self._closed = True
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._sock.close()

    # ------------------------------------------------------------ internals

    def partial_state_count(self) -> int:
        """Live partial-reassembly entries (the leak probe the wire-v2
        tests assert on after timeouts/disconnects)."""
        with self._flock:
            return len(self._partial)

    def _read_loop(self) -> None:
        buf = bytearray()
        try:
            while True:
                chunk = self._sock.recv(65536)
                if not chunk:
                    raise PeerLinkError("peer closed the link")
                buf += chunk
                while len(buf) >= 4:
                    (length,) = struct.unpack_from("<I", buf, 0)
                    if len(buf) - 4 < length:
                        break
                    payload = memoryview(buf)[4:4 + length]
                    rid, method = struct.unpack_from("<QB", payload, 0)
                    if method >= WIRE_GREETING:
                        self._control_frame(method, payload)
                        del payload
                        del buf[:4 + length]
                        continue
                    resps = decode_response_frame(payload)
                    del payload
                    del buf[:4 + length]
                    with self._flock:
                        fut = self._futures.pop(rid, None)
                        # a whole v1 frame is authoritative (native fast
                        # path, server-side error fill): any partial
                        # reassembly it supersedes is dropped
                        self._expected.pop(rid, None)
                        self._partial.pop(rid, None)
                    if fut is not None and not fut.done():
                        fut.set_result(resps)
        except Exception as e:  # noqa: BLE001 — reader dies: fail all waiters
            self._fail(e)

    def _control_frame(self, method: int, payload: memoryview) -> None:
        """One v2 control frame off the read loop (layouts: docs/wire.md).
        Unknown control methods skip — forward compatibility; a raised
        exception (out-of-contract partial stream) fails the link."""
        if method == WIRE_GREETING:
            # version rides in the status column of the v1-shaped greeting
            (server_max,) = struct.unpack_from("<i", payload, 11)
            if self._want_v2 and server_max >= 2 and not self._closed:
                with self._wlock:
                    self._sock.sendall(
                        struct.pack("<IQBH", 11, 0, WIRE_HELLO, 2))
                self.wire_version = 2
                if self._recorder is not None:
                    self._recorder.emit("wire.v2_upgrade", peer=self.address,
                                        server_max=int(server_max))
            return
        if method != WIRE_PARTIAL:
            return
        rid, seq, base, fin, items = decode_partial_frame(payload)
        fire = None
        rows: list = []
        with self._flock:
            n_exp = self._expected.get(rid)
            if n_exp is None:
                # the caller already gave up (timeout) or the rid was
                # superseded by a whole frame: drop, never reassemble
                self._partial.pop(rid, None)
                return
            st = self._partial.get(rid)
            if st is None:
                st = self._partial[rid] = [[None] * n_exp, 0]
            rows = st[0]
            if seq != st[1] or base + len(items) > n_exp:
                raise PeerLinkError(
                    f"partial reply out of contract (rid={rid} seq={seq} "
                    f"want={st[1]} base={base} n={len(items)}/{n_exp})")
            st[1] = seq + 1
            rows[base:base + len(items)] = items
            if fin:
                if any(r is None for r in rows):
                    raise PeerLinkError(
                        f"final partial left holes (rid={rid})")
                del self._partial[rid]
                del self._expected[rid]
                fire = self._futures.pop(rid, None)
        if fire is not None and not fire.done():
            fire.set_result(rows)

    def _fail(self, exc: Exception) -> None:
        self._closed = True
        with self._flock:
            futs, self._futures = self._futures, {}
            self._expected.clear()
            self._partial.clear()
        for fut in futs.values():
            if not fut.done():
                fut.set_exception(PeerLinkError(str(exc)))


def read_front_profile(lib, handle) -> Optional[List[int]]:
    """The C++ front's histograms and counters as pls_profile writes them
    (obs/profile.py Profiler.front_totals reads the layout)."""
    from gubernator_tpu.obs.profile import FRONT_PROFILE_LEN

    buf = (ctypes.c_longlong * FRONT_PROFILE_LEN)()
    if lib.pls_profile(handle, buf, FRONT_PROFILE_LEN) != FRONT_PROFILE_LEN:
        return None
    return list(buf)


class _PullCtx:
    """One pull's buffers + reply bookkeeping: rows post to the wire as
    their sub-windows finalize (pls_send_partial), and in-flight launches
    may outlive _handle_batch, so the pull's buffer set and its
    error/metadata sidecars must live until every launch referencing
    them drains (live == 0)."""

    __slots__ = ("b", "got", "errs", "metas", "live", "posted")

    def __init__(self, b: dict, got: int):
        self.b = b
        self.got = got
        self.errs: List[tuple] = []   # (item index, error bytes)
        self.metas: List[tuple] = []  # (item index, pb metadata bytes)
        self.live = 0    # launches in flight referencing these buffers
        self.posted = 0  # rows handed to pls_send_partial so far


class PeerLinkService:
    """The server: C++ transport + Python batch workers over an Instance."""

    MAX_N = 8192  # per-pull item cap (several frames aggregate per pull)
    KEY_CAP = 2 << 20  # > one max frame's keys (4096 items x 255 B)

    def __init__(self, instance, port: int = 0, workers: int = 2,
                 grpc_port: Optional[int] = None, grpc_host: str = "",
                 metrics=None, pipeline_depth=None, pipeline_scan=None,
                 wire_v2: bool = True):
        from gubernator_tpu import native
        from gubernator_tpu.native import load_peerlink
        from gubernator_tpu.service.combiner import (
            DEFAULT_PIPELINE_DEPTH,
            _env_depth,
            _env_scan,
        )

        # Depth-N pipelined columnar serving (_columnar_chunk): the depth/
        # scan knobs are SHARED with the object-path combiner
        # (GUBER_PIPELINE_DEPTH / GUBER_PIPELINE_SCAN — the daemon passes
        # the combiner's autotuned winner through pipeline_depth, so both
        # wire protocols ride one resolved setting). Depth 1 (pinned or
        # auto-degraded) is lock-step submit/complete.
        self._col_depth = _env_depth(pipeline_depth) or DEFAULT_PIPELINE_DEPTH
        self._col_scan = _env_scan(pipeline_scan)

        # wire contract v2 (docs/wire.md): the server greets on accept and
        # streams seq-numbered partial replies to the connections that
        # answer HELLO; every other connection gets whole v1 frames,
        # accumulated in C++. wire_v2=False is the interop tests' old
        # binary (a server that never greets): it goes to pls_start2 and
        # selects nothing in Python; no production caller passes it.
        self._lib = load_peerlink()
        bound = ctypes.c_int(0)
        self._handle = self._lib.pls_start2(port, ctypes.byref(bound),
                                            2 if wire_v2 else 1)
        if not self._handle:
            raise PeerLinkError(f"peerlink: cannot bind port {port}")
        self.port = bound.value
        # wire-compatible gRPC/HTTP/2 front (native/peerlink.cpp): real
        # gubernator clients connect HERE; hot unary calls are parsed and
        # decided in C, the rest punts to the Python servicers below
        self.grpc_port: Optional[int] = None
        self._metrics = metrics
        # resolved once (minimal Metrics objects in tests may not carry
        # them)
        self._mt_stall = getattr(metrics, "peerlink_pull_boundary_stalls",
                                 None)
        self._mt_span = getattr(metrics, "peerlink_partial_span_items",
                                None)
        if grpc_port is not None:
            gp = self._lib.pls_start_grpc(self._handle, grpc_port,
                                          grpc_host.encode())
            if gp < 0:
                self._lib.pls_stop(self._handle)
                self._lib.pls_free(self._handle)
                raise PeerLinkError(
                    f"peerlink: cannot bind gRPC port {grpc_port}")
            self.grpc_port = gp
        self.instance = instance
        # the cycle profiler (obs/profile.py) reads this front's own
        # histograms at scrape time, and while a capture runs the pull
        # loop writes its spans (front.pull_wait, post) into it
        from gubernator_tpu.obs.profile import Profiler

        self._prof = getattr(instance, "profiler", None) \
            or Profiler(enabled=False)
        self._prof.attach_front(self.front_profile)
        # flight recorder (obs/events.py): columnar pipeline cuts and
        # fill stalls become causal events alongside the stat counters
        self._recorder = getattr(instance, "recorder", None)
        # /v1/debug/vars "wire" section (obs/introspect.py) reads live
        # wire-contract state off this back-reference
        instance.peerlink_service = self
        self.stats = {"batches": 0, "requests": 0, "errors": 0,
                      # pipelined columnar serving (_columnar_chunk)
                      "columnar_windows": 0, "columnar_groups": 0,
                      "columnar_cuts": 0, "columnar_fill_stalls": 0,
                      # what the columnar chunks handed back
                      # (_leftover_items)
                      "leftover_items": 0,
                      # times the worker had launches in flight but
                      # nothing new to pull
                      "pull_boundary_stalls": 0}
        if metrics is not None and hasattr(metrics, "set_peerlink_stats"):
            # exports batches/requests/errors as peerlink_* families
            metrics.set_peerlink_stats(lambda: self.stats)
        if metrics is not None and hasattr(metrics,
                                           "peerlink_columnar_depth"):
            metrics.peerlink_columnar_depth.set(self._col_depth)
        self._public_fast = False  # method-0 owner paths (standalone only)
        # native lone-request fast path: 1-item peer-hop frames decide in
        # the C++ IO thread against the engine's directory row mirrors
        # (keydir.cpp decide_one) — no Python wakeup, no kernel dispatch.
        # Misses fall through to the worker path below, which re-seeds.
        self._seed_engine = None
        cb = getattr(instance, "columnar_backend", None)
        eng = cb() if callable(cb) else None
        if eng is not None:
            # the PUBLIC lean surface (method 0) needs routing; while this
            # node owns every key the columnar owner path (and, on the
            # single-table engine, the IO-thread mirror path) can serve it
            # too — re-armed whenever membership changes
            self._rearm_public()
            if hasattr(instance, "on_peers_change"):
                instance.on_peers_change(self._rearm_public)
        if eng is not None and hasattr(eng, "seed_mirror") and \
                hasattr(eng.directory, "_kd"):
            kd_lib = native.load_library()
            fn = ctypes.cast(kd_lib.keydir_decide_one,
                             ctypes.c_void_p).value
            self._lib.pls_set_native(
                self._handle, fn, eng.directory._kd, _COLUMNAR_SLOW_MASK)
            self._seed_engine = eng
        self._stop = False
        self._threads = []
        for i in range(workers):
            t = threading.Thread(target=self._worker, name=f"peerlink-{i}",
                                 daemon=True)
            t.start()
            self._threads.append(t)
        if self.grpc_port is not None:
            self._refresh_health()
            if hasattr(instance, "on_peers_change"):
                instance.on_peers_change(self._refresh_health)
            t = threading.Thread(target=self._raw_worker,
                                 name="peerlink-grpc-raw", daemon=True)
            t.start()
            self._threads.append(t)

    def native_hits(self) -> int:
        """Lone requests answered by the C++ IO thread (no Python)."""
        return int(self._lib.pls_native_hits(self._handle))

    def wire_partial_posts(self) -> int:
        """v2 partial frames streamed so far (C++ counter)."""
        return int(self._lib.pls_partial_posts(self._handle))

    def wire_pending_count(self) -> int:
        """Live C++ reply-assembly entries across every conn — the leak
        probe the wire-v2 tests assert returns to zero."""
        return int(self._lib.pls_pending_count(self._handle))

    def front_profile(self) -> Optional[List[int]]:
        """What Profiler.attach_front reads; None once closed."""
        if self._stop:
            return None
        return read_front_profile(self._lib, self._handle)

    def wire_debug(self) -> dict:
        """The /v1/debug/vars "wire" section: negotiated-contract state
        and the partial-streaming counters."""
        return {
            "v2_conns": int(self._lib.pls_v2_conns(self._handle)),
            "partial_posts": self.wire_partial_posts(),
            "pending_replies": self.wire_pending_count(),
            "pull_boundary_stalls": self.stats["pull_boundary_stalls"],
        }

    def _rearm_public(self) -> None:
        sole = bool(getattr(self.instance, "is_sole_owner",
                            lambda: False)())
        self._public_fast = sole
        self._lib.pls_set_native_public(self._handle, int(sole))

    # ------------------------------------------- gRPC front (raw punts)

    def _refresh_health(self) -> None:
        """Re-publish the pre-serialized HealthCheckResp the C IO thread
        answers /pb.gubernator.V1/HealthCheck with (refreshed on peer
        changes and on raw-worker idle ticks — sub-second staleness)."""
        try:
            from gubernator_tpu.service.convert import health_to_pb

            blob = health_to_pb(self.instance.health_check()) \
                .SerializeToString()
            self._lib.pls_set_health(self._handle, blob, len(blob))
        except Exception:  # noqa: BLE001 — C falls back to the raw path
            self._lib.pls_set_health(self._handle, b"", 0)

    def _count_rpc(self, method: str, ok: bool, n: int = 1) -> None:
        """Feed the daemon's Prometheus counters (the grpcio interceptor
        did this when it served the port; the native front reports the
        same families so dashboards keep working)."""
        m = self._metrics
        if m is None or n <= 0:
            return
        try:
            m.grpc_request_counts.labels(
                status="ok" if ok else "error", method=method).inc(n)
        except Exception:  # noqa: BLE001 — metrics must never break serving
            pass

    def _raw_worker(self) -> None:
        """Serve the calls the C gRPC front punts (UpdatePeerGlobals,
        unknown fields/methods, oversized) through the SAME servicer
        logic the grpcio server binds — wire compatibility has one
        implementation; C is only a fast lane in front of it."""
        from gubernator_tpu.service import server as srv
        from gubernator_tpu.service.pb import gubernator_pb2 as pb
        from gubernator_tpu.service.pb import peers_pb2 as peers_pb

        v1 = srv.V1Servicer(self.instance)
        peers = srv.PeersV1Servicer(self.instance)
        path_buf = ctypes.create_string_buffer(1024)
        body_buf = ctypes.create_string_buffer(5 << 20)
        path_len = ctypes.c_int(0)
        conn = ctypes.c_ulonglong(0)
        sid = ctypes.c_uint(0)
        last_health = 0.0
        while not self._stop:
            n = self._lib.pls_next_raw(
                self._handle, 500_000, path_buf, len(path_buf),
                ctypes.byref(path_len), body_buf, len(body_buf),
                ctypes.byref(conn), ctypes.byref(sid))
            if n == -1:
                return  # stopping
            # time-based refresh keeps HealthCheck honest even under
            # SUSTAINED punted traffic (no idle ticks to piggyback on)
            now = time.monotonic()
            if now - last_health >= 1.0:
                self._refresh_health()
                last_health = now
            if n < 0:
                continue
            path = path_buf.raw[:path_len.value].decode("ascii", "replace")
            body = body_buf.raw[:n]
            status, msg, resp = 0, b"", b""
            try:
                if path == "/pb.gubernator.V1/GetRateLimits":
                    out = v1.GetRateLimits(
                        pb.GetRateLimitsReq.FromString(body), _RawCtx())
                elif path == "/pb.gubernator.V1/HealthCheck":
                    out = v1.HealthCheck(
                        pb.HealthCheckReq.FromString(body), _RawCtx())
                elif path == "/pb.gubernator.PeersV1/GetPeerRateLimits":
                    out = peers.GetPeerRateLimits(
                        peers_pb.GetPeerRateLimitsReq.FromString(body),
                        _RawCtx())
                elif path == "/pb.gubernator.PeersV1/UpdatePeerGlobals":
                    out = peers.UpdatePeerGlobals(
                        peers_pb.UpdatePeerGlobalsReq.FromString(body),
                        _RawCtx())
                elif path == "/pb.gubernator.V1/Debug":
                    # raw-bytes RPC (identity serializers, no protoc): the
                    # response is already the wire payload
                    out = None
                    resp = v1.Debug(body, _RawCtx())
                else:
                    raise _RawAbort(12, f"unknown method {path}")
                if out is not None:
                    resp = out.SerializeToString()
            except _RawAbort as e:
                status, msg = e.code, e.details.encode()
            except Exception as e:  # noqa: BLE001
                log.exception("grpc raw call failed")
                status, msg = 13, str(e).encode()
            self._count_rpc(path.rsplit("/", 1)[-1], status == 0)
            if self._metrics is not None:
                try:
                    self._metrics.grpc_request_duration.labels(
                        method=path.rsplit("/", 1)[-1]).observe(
                            (time.monotonic() - now) * 1e3)
                except Exception:  # noqa: BLE001
                    pass
            try:
                self._lib.pls_send_raw(self._handle, conn.value, sid.value,
                                       resp, len(resp), status, msg)
            except Exception:  # noqa: BLE001
                log.exception("grpc raw reply failed")

    def close(self) -> None:
        self._stop = True
        self._prof.attach_front(None)  # no scrape may touch the freed handle
        if getattr(self.instance, "peerlink_service", None) is self:
            self.instance.peerlink_service = None
        # a stale peer-change listener would poke the freed native handle
        if hasattr(self.instance, "off_peers_change"):
            self.instance.off_peers_change(self._rearm_public)
            if self.grpc_port is not None:
                self.instance.off_peers_change(self._refresh_health)
        self._lib.pls_stop(self._handle)  # wakes blocked pullers (-1)
        for t in self._threads:
            t.join(timeout=2.0)
        if not any(t.is_alive() for t in self._threads):
            # free only once no puller can touch the handle again
            self._lib.pls_free(self._handle)

    # ------------------------------------------------------------ internals

    def _mk_pull_bufs(self) -> dict:
        """One pull-buffer set: request columns in, response rows out,
        plus the pre-built ctypes argument tuples pls_next_batch and
        pls_send_responses consume (pointers are stable — the arrays
        never reallocate). The worker rotates a ring of sets so the next
        pull preps while launches against earlier sets are still in
        flight."""
        n = self.MAX_N
        b = {
            "keys": ctypes.create_string_buffer(self.KEY_CAP),
            "key_off": np.zeros(n + 1, np.int32),
            "name_len": np.zeros(n, np.int32),
            "hits": np.zeros(n, np.int64),
            "limit": np.zeros(n, np.int64),
            "duration": np.zeros(n, np.int64),
            "algorithm": np.zeros(n, np.int32),
            "behavior": np.zeros(n, np.int32),
            "method": np.zeros(n, np.int32),
            "idx": np.zeros(n, np.int32),
            "conn": np.zeros(n, np.uint64),
            "rid": np.zeros(n, np.uint64),
            # response buffers, reused across batches (allocation costs
            # real microseconds on the lone-call latency path)
            "status": np.zeros(n, np.int32),
            "r_limit": np.zeros(n, np.int64),
            "r_remaining": np.zeros(n, np.int64),
            "r_reset": np.zeros(n, np.int64),
            "err_off": np.zeros(n + 1, np.int32),
            "meta_off": np.zeros(n + 1, np.int32),
        }

        def p(a):
            return a.ctypes.data_as(ctypes.c_void_p)

        b["args"] = (b["keys"], self.KEY_CAP, p(b["key_off"]),
                     p(b["name_len"]), p(b["hits"]), p(b["limit"]),
                     p(b["duration"]), p(b["algorithm"]), p(b["behavior"]),
                     p(b["method"]), p(b["idx"]), p(b["conn"]), p(b["rid"]),
                     n)
        b["resp_ptrs"] = (p(b["conn"]), p(b["rid"]), p(b["idx"]),
                          p(b["status"]), p(b["r_limit"]),
                          p(b["r_remaining"]), p(b["r_reset"]),
                          p(b["err_off"]))
        b["meta_ptr"] = p(b["meta_off"])
        return b

    def _worker(self) -> None:
        """The serving loop: pull, handle, post. A pull's run of
        one-window chunks (every pull at the shipped widths) is launched,
        collected and posted inside the pull, a scan group at a time
        (_columnar_run): nothing of it is in flight when the worker comes
        back here. The launches of a chunk WIDER than one window
        (_columnar_chunk's pipeline) may stay in flight ACROSS pull
        boundaries — while a group rides the device its earlier rows are
        already on the wire (_post_span), and the next pull preps into a
        DIFFERENT buffer set of the ring. A set is reused only once no
        in-flight launch references it, so with more sets than pipeline
        depth the ring blocks only when the device is the bottleneck
        anyway. The worker polls for new frames while work is in flight
        and counts a pull_boundary_stall each time the poll comes back
        empty."""
        depth = self._col_depth
        nsets = min(depth, 4) + 1
        sets = [self._mk_pull_bufs() for _ in range(nsets)]
        ws = {
            # (eng, handle, gspans, ctx, method) in dispatch order — the
            # shared pipeline every columnar chunk launches into
            "inflight": collections.deque(),
            # worker-level staging ring with a MONOTONIC slot cursor:
            # per-chunk cursors would reuse slot 0 across chunks/pulls
            # while a launch still holds it
            "staging": [dict() for _ in range(depth + 2)],
            "seq": 0,
            # the staging stacks of _columnar_run's groups, one a depth:
            # a group is collected before the worker launches the next
            "run_staging": {},
            "ctxs": [None] * nsets,  # the ctx last prepped into each set
            "cur": 0,
        }
        prof = self._prof
        while not self._stop:
            cur = ws["cur"]
            old = ws["ctxs"][cur]
            while old is not None and old.live > 0 and ws["inflight"]:
                self._drain_at_boundary(ws)  # free this set's buffers
            b = sets[cur]
            if ws["inflight"]:
                # a poll, not a wait: it gets no span
                got = self._lib.pls_next_batch(self._handle, 0, *b["args"])
                if got == 0:
                    # launches in flight, nothing new to pull: count the
                    # boundary stall, retire the oldest launch, poll again
                    self.stats["pull_boundary_stalls"] += 1
                    if self._mt_stall is not None:
                        self._mt_stall.inc()
                    self._drain_at_boundary(ws)
                    continue
            else:
                with prof.span("front.pull_wait"):
                    got = self._lib.pls_next_batch(
                        self._handle, 200_000, *b["args"])  # 200 ms idle tick
            if got < 0:
                try:
                    self._drain_all(ws)  # stopping: settle device work
                except Exception:  # noqa: BLE001
                    log.exception("peerlink drain on stop failed")
                return
            if got == 0:
                continue
            ctx = _PullCtx(b, got)
            ws["ctxs"][cur] = ctx
            ws["cur"] = (cur + 1) % nsets
            # the parent of every span this pull's handling writes on
            # this thread: its self time (duration less its children) is
            # the loop's own Python
            with prof.span("pull"):
                try:
                    self._handle_batch(got, b, ctx=ctx, ws=ws)
                except Exception:  # noqa: BLE001 — a worker must never die
                    log.exception("peerlink batch failed")
                    self.stats["errors"] += 1
                    self._recover_batch(ws, ctx)

    def _drain_at_boundary(self, ws: dict) -> None:
        """_drain_one_entry between pulls, where no pull's try is open: a
        collect, leftover retirement or post that raises must not kill
        the worker, and the popped launch's rows still need an answer."""
        try:
            self._drain_one_entry(ws)
        except Exception:  # noqa: BLE001 — a worker must never die
            log.exception("peerlink drain failed")
            self.stats["errors"] += 1
            self._recover_batch(ws, None)

    def _recover_batch(self, ws: dict, ctx: Optional[_PullCtx]) -> None:
        """Exception recovery: settle the shared pipeline, then answer
        EVERY row of the failed pull with an error reply via
        pls_send_responses — rids already streamed to completion are
        skipped by C++ (their pending entries are gone), partially
        streamed rids complete as an authoritative whole error frame,
        untouched rids get the plain v1 error fill. An EARLIER pull whose
        launch was popped and then failed to collect (ctx is None when
        that happened between pulls) has rows nobody posted: it gets the
        same error fill. Nothing hangs."""
        try:
            self._drain_all(ws)
        except Exception:  # noqa: BLE001 — drain blew up too: drop refs
            log.exception("peerlink pipeline drain failed")
            ws["inflight"].clear()
            for c2 in ws["ctxs"]:
                if c2 is not None:
                    c2.live = 0
        for c2 in ws["ctxs"]:
            if c2 is not None and c2 is not ctx and c2.posted < c2.got:
                self._error_fill(c2)
        if ctx is not None:
            self._error_fill(ctx)

    def _error_fill(self, ctx: _PullCtx) -> None:
        """Answer every row of one pull with the internal-failure reply
        (C++ skips the rids that already completed)."""
        b, got = ctx.b, ctx.got
        err_buf = self._fail_batch(got, b)
        b["meta_off"][:got + 1] = 0
        try:
            self._lib.pls_send_responses(
                self._handle, got, *b["resp_ptrs"], err_buf,
                b["meta_ptr"], b"")
        except Exception:  # noqa: BLE001
            log.exception("peerlink send_responses failed")
            self.stats["errors"] += 1
        ctx.errs.clear()
        ctx.metas.clear()
        ctx.posted = ctx.got

    def _drain_one_entry(self, ws: dict) -> Optional[str]:
        """Collect the OLDEST in-flight launch (dispatch order = per-key
        order), retire its cut leftovers through the object path, and
        post the group's finalized rows to the wire. Returns the
        handle's over-commit message (or None)."""
        eng, handle, gspans, ctx, m = ws["inflight"].popleft()
        ctx.live -= 1
        if not gspans:  # consumed nothing (over-commit at window 0)
            return handle[1]
        b = ctx.b
        outs = [self._col_outs(b, s0, s1) for s0, s1 in gspans]
        leftovers = eng.collect_columnar_windows(handle, outs)
        for (s0, _s1), left in zip(gspans, leftovers):
            if left is not None and len(left):
                self._leftover_items(m, s0, left.tolist(), b, ctx.errs,
                                     ctx.metas)
        self._post_span(ctx, gspans[0][0], gspans[-1][1])
        return handle[1]

    def _drain_all(self, ws: dict) -> Optional[str]:
        """Pipeline barrier: drain every in-flight launch in dispatch
        order. Returns the last over-commit message seen (or None)."""
        msg = None
        while ws["inflight"]:
            msg = self._drain_one_entry(ws) or msg
        return msg

    def _post_span(self, ctx: _PullCtx, lo: int, hi: int) -> None:
        """Post finalized rows [lo, hi) of a pull to the wire, one
        pls_send_partial per (conn, rid) run: C++ streams the span NOW to
        a v2 peer (seq-numbered partial frame) and accumulates the v1/H2
        whole-frame contract otherwise. base is frame-relative
        (b["idx"]), so one rid's runs may post in any base order across
        calls — seq keeps the client's reassembly honest."""
        if hi <= lo:
            return
        b = ctx.b
        rids, conns, idxs = b["rid"], b["conn"], b["idx"]
        cast = ctypes.c_void_p
        with self._prof.span("post"):
            # a run must not cross a FRAME boundary: a client may reuse a
            # rid back-to-back (duplicate-rid fuzz), which (conn, rid)
            # equality alone would merge into one oversized span that the
            # C++ bounds check rejects — and the rid then never completes.
            # Within a frame the pull keeps items contiguous, so idx
            # advances by exactly 1; anything else starts a new frame.
            # One pass over the columns finds every run's end (a Python
            # step per row held the GIL ~0.5 ms a 1000-row span).
            r, c, x = rids[lo:hi], conns[lo:hi], idxs[lo:hi]
            ends = np.flatnonzero(
                (r[1:] != r[:-1]) | (c[1:] != c[:-1])
                | (x[1:] != x[:-1] + 1)) + (lo + 1)
            i = lo
            for e in ends.tolist() + [hi]:
                eo, eb = self._run_sidecar(ctx.errs, i, e)
                mo, mb = self._run_sidecar(ctx.metas, i, e)
                self._lib.pls_send_partial(
                    self._handle, int(conns[i]), int(rids[i]),
                    int(idxs[i]), e - i,
                    b["status"][i:e].ctypes.data_as(cast),
                    b["r_limit"][i:e].ctypes.data_as(cast),
                    b["r_remaining"][i:e].ctypes.data_as(cast),
                    b["r_reset"][i:e].ctypes.data_as(cast),
                    eo.ctypes.data_as(cast), eb, mo.ctypes.data_as(cast), mb)
                if self._mt_span is not None:
                    self._mt_span.observe(e - i)
                i = e
        ctx.posted += hi - lo

    @staticmethod
    def _run_sidecar(pairs: list, lo: int, hi: int):
        """Extract the (index, bytes) sidecar entries for items [lo, hi)
        as a span-relative offset column + blob, REMOVING them from the
        list (each row posts exactly once). Entries may sit out of index
        order — inline object retirement interleaves with group drains."""
        n = hi - lo
        off = np.zeros(n + 1, np.int32)
        if not pairs:
            return off, b""
        mine: Dict[int, bytes] = {}
        keep = []
        for t in pairs:
            if lo <= t[0] < hi:
                mine[t[0]] = t[1]
            else:
                keep.append(t)
        if not mine:
            return off, b""
        pairs[:] = keep
        total = 0
        blob = []
        for o in range(n):
            seg = mine.get(lo + o)
            if seg:
                blob.append(seg)
                total += len(seg)
            off[o + 1] = total
        return off, b"".join(blob)

    @staticmethod
    def _fail_batch(got: int, b: dict) -> bytes:
        """Last-resort response fill: every item in the pull gets an error
        reply so no client (or C++ pending entry) is left hanging."""
        msg = b"peerlink: internal batch failure"
        b["status"][:got] = 0
        b["r_limit"][:got] = 0
        b["r_remaining"][:got] = 0
        b["r_reset"][:got] = 0
        b["err_off"][:got + 1] = np.arange(got + 1, dtype=np.int32) * len(msg)
        return msg * got

    def _handle_batch(self, got: int, b: dict, ctx: _PullCtx,
                      ws: dict) -> None:
        """Decode -> handler calls -> fill the pull's response buffers.
        Every row posts to the wire THROUGH this call via _post_span —
        per chunk for carrier/object chunks, per group for columnar
        ones; only a chunk wider than one window may leave clean groups
        in flight in ws when it returns.

        Peer-hop chunks ride the COLUMNAR path when the backend offers it
        (Engine.launch_columnar_windows / submit_columnar): the wire
        columns go through the GIL-free C prep straight to the device and
        the response rows scatter back into these buffers; no
        RateLimitReq/RateLimitResp objects at all on the hot path. A run
        of several chunks of one method — the calls of up to
        MAX_BATCH_SIZE requests a pull brought together — is handed over
        as scan groups, ONE launch a group (_columnar_run: one hold of
        the engine lock, one enqueue, one wait, one copy back), where the
        backend's group is one launch; a lone chunk is served lock-step,
        and one wider than the engine's widest window scan-grouped and
        depth-pipelined (_columnar_chunk). Items the columnar prep can't
        take (invalid, gregorian, GLOBAL/MULTI_REGION, duplicate
        occurrences) run through the request-object path AFTER the
        packed round."""
        self.stats["batches"] += 1
        self.stats["requests"] += got
        t_batch0 = time.perf_counter()
        if self._metrics is not None and got:
            # one RPC per distinct frame in the pull (rid changes mark
            # frame boundaries; the pull preserves frame order), counted
            # per method. Both wire protocols (gRPC front + columnar
            # link) feed this queue; method is the honest label either
            # way (the grpcio interceptor also counted peer hops under
            # their method name).
            rids = b["rid"][:got]
            conns = b["conn"][:got]
            meth = b["method"][:got] & ~METHOD_FLAGS  # count by base method
            starts = np.ones(got, bool)
            starts[1:] = ((rids[1:] != rids[:-1])
                          | (conns[1:] != conns[:-1]))
            n0 = int(np.count_nonzero(starts & (meth == 0)))
            n1 = int(np.count_nonzero(starts & (meth != 0)))
            self._count_rpc("GetRateLimits", True, n0)
            self._count_rpc("GetPeerRateLimits", True, n1)
            self._frames_in_batch = (n0, n1)
        method = b["method"]
        errs, metas = ctx.errs, ctx.metas  # live with the pull's buffers
        cb = getattr(self.instance, "columnar_backend", None)
        eng = cb() if callable(cb) else None

        # a lone non-slow miss seeds the IO-thread mirror below. The seed
        # snapshots the key's device row, so it must install BEFORE the
        # reply reaches the wire: once the client can send the key's next
        # request, a late seed would overwrite natively-applied hits with
        # the stale snapshot
        lone_seed = (
            got == 1 and self._seed_engine is not None
            and (int(method[0]) == METHOD_GET_PEER_RATE_LIMITS
                 or (int(method[0]) == METHOD_GET_RATE_LIMITS
                     and self._public_fast))
            and not (int(b["behavior"][0]) & _COLUMNAR_SLOW_MASK))

        # one handler call per contiguous same-method run (chunked at the
        # batch cap — the aggregation may have merged many frames). The
        # runs' ends are found in one pass: a Python step per item held
        # the GIL ~0.15 ms a 1000-item chunk, beside the sibling worker
        meth = method[:got]
        run_ends = (np.flatnonzero(meth[1:] != meth[:-1]) + 1).tolist()
        run_ends.append(got)
        run = 0
        j = 0
        while j < got:
            m = int(method[j])
            if run_ends[run] <= j:
                run += 1
            k = min(run_ends[run], j + MAX_BATCH_SIZE)
            # method-1 chunks always qualify for the columnar owner path;
            # method-0 (public) chunks qualify only while this node owns
            # every key (no routing needed — standalone deployments)
            columnar_ok = eng is not None and (
                m == METHOD_GET_PEER_RATE_LIMITS
                or (m == METHOD_GET_RATE_LIMITS and self._public_fast))
            if m & METHOD_FLAGS:
                # flagged frames (trace and/or deadline): decode the
                # carrier item(s), install the contexts, ride the combiner
                # (a traced window's wait is part of the phase picture; a
                # budgeted window's wait is where its budget dies)
                self._carrier_chunk(m, j, k, b, errs, metas)
                # post AFTER the whole carrier frame handling — the
                # lease grant overwrites its lane last
                self._post_span(ctx, j, k)
            elif lone_seed:
                # seed-ordering: decide lock-step WITHOUT posting; the
                # seed block below runs first, then the post
                if not (columnar_ok and not self._saturated()
                        and self._columnar_chunk_lockstep(
                            m, eng, [(j, k)], k, b, errs, metas)):
                    self._object_chunk(m, j, k, b, errs, metas)
            elif (columnar_ok and run_ends[run] > k
                    and self._groups_a_run(eng, k - j)):
                # more chunks of this method behind this one: the run is
                # handed over as scan groups, one launch a group, all of
                # it collected and posted before the next chunk is cut
                k = run_ends[run]
                self._columnar_run(m, eng, j, k, ctx, ws)
            else:
                self._chunk_alone(m, eng if columnar_ok else None, j, k,
                                  ctx, ws)
            j = k

        if lone_seed:
            # a lone peer-hop reached Python = the IO-thread fast path
            # missed (cold/invalidated mirror). Seed it so the NEXT lone
            # request for this key decides natively.
            try:
                lo, hi = int(b["key_off"][0]), int(b["key_off"][1])
                split = lo + int(b["name_len"][0])
                self._seed_engine.seed_mirror(
                    b["keys"][lo:split].decode() + "_"
                    + b["keys"][split:hi].decode())
            except Exception:  # noqa: BLE001 — seeding is best-effort
                pass
            self._post_span(ctx, 0, got)  # mirror installed: post now

        if self._metrics is not None and got:
            # every frame in the pull experienced ~this service time (the
            # batch IS the unit of work); native-lane RPCs never reach
            # Python and carry no histogram sample — documented limit
            ms = (time.perf_counter() - t_batch0) * 1e3
            n0, n1 = getattr(self, "_frames_in_batch", (0, 0))
            try:
                self._metrics.peerlink_stage_ms.labels(
                    stage="handle").observe(ms)
            except Exception:  # noqa: BLE001
                pass
            try:
                if n0:
                    self._metrics.grpc_request_duration.labels(
                        method="GetRateLimits").observe(ms)
                if n1:
                    self._metrics.grpc_request_duration.labels(
                        method="GetPeerRateLimits").observe(ms)
            except Exception:  # noqa: BLE001
                pass

    def _chunk_spans(self, eng, j: int, k: int) -> List[tuple]:
        """Split [j, k) into engine sub-windows along the pow2 bucket
        ladder (models/prep.py bucket_splits): a chunk one item over a
        window boundary never mints an off-ladder XLA shape mid-serve,
        even on a capacity-capped (non-pow2 max_width) engine."""
        from gubernator_tpu.models.prep import bucket_splits

        hi = int(getattr(eng, "max_width", 0)) or (k - j)
        lo = int(getattr(eng, "min_width", 1)) or 1
        spans = []
        s0 = j
        for ln in bucket_splits(k - j, min(lo, hi), hi):
            spans.append((s0, s0 + ln))
            s0 += ln
        return spans

    def _col_window(self, b: dict, s0: int, s1: int) -> tuple:
        """One sub-window's wire columns, as launch_columnar_windows /
        submit_columnar consume them (views into the pull buffers)."""
        return (s1 - s0, b["keys"], b["key_off"][s0:s1 + 1],
                b["name_len"][s0:s1], b["hits"][s0:s1],
                b["limit"][s0:s1], b["duration"][s0:s1],
                b["algorithm"][s0:s1], b["behavior"][s0:s1])

    @staticmethod
    def _col_outs(b: dict, s0: int, s1: int) -> tuple:
        """One sub-window's response-row buffers (views into the pull
        buffers — disjoint per span, so in-flight launches never race)."""
        return (b["status"][s0:s1], b["r_limit"][s0:s1],
                b["r_remaining"][s0:s1], b["r_reset"][s0:s1])

    def _col_error_fill(self, msg: bytes, s0: int, k: int, b: dict,
                        errs: list) -> None:
        """Error-reply fill for items [s0, k) of a chunk (over-commit)."""
        b["status"][s0:k] = 0
        b["r_limit"][s0:k] = 0
        b["r_remaining"][s0:k] = 0
        b["r_reset"][s0:k] = 0
        errs.extend((i, msg) for i in range(s0, k))

    def _saturated(self) -> bool:
        """Admission saturated: a columnar chunk is demoted to the object
        path, whose admission gate answers RESOURCE_EXHAUSTED error rows
        in microseconds — the zero-object fast path must not become the
        hole overload pours through (one int compare when off)."""
        adm = getattr(self.instance, "admission", None)
        return (adm is not None and adm.enabled
                and adm.level() >= adm.SATURATED)

    def _chunk_alone(self, m: int, eng, j: int, k: int, ctx: _PullCtx,
                     ws: dict) -> None:
        """One chunk by itself: columnar where `eng` (the columnar
        backend, None where the chunk may not ride it) takes it, else
        through the request-object path. The columnar path posts its own
        spans as groups drain (and may leave clean groups in flight);
        an object chunk posts whole."""
        if not (eng is not None
                and self._columnar_chunk(m, eng, j, k, ctx, ws)):
            self._object_chunk(m, j, k, ctx.b, ctx.errs, ctx.metas)
            self._post_span(ctx, j, k)

    def _scan_cap(self, eng) -> int:
        """The most windows one group launch may carry: the shared
        GUBER_PIPELINE_SCAN setting, under the engine's own limit."""
        return min(self._col_scan, int(getattr(eng, "_MAX_SCAN", 0) or 1))

    def _groups_a_run(self, eng, widest: int) -> bool:
        """Whether a run of chunks, the widest `widest` items, is served
        as scan groups (_columnar_run): the backend says a group is one
        launch (`columnar_group_is_one_launch`: Engine's is, the mesh's
        is a launch a window), the group shapes are warm (the daemon warms
        them where the pipeline depth is not 1), and a chunk is one window
        of the ladder. Nothing here is a setting of its own."""
        return (self._col_depth > 1 and self._scan_cap(eng) > 1
                and getattr(eng, "columnar_group_is_one_launch", False)
                and len(self._chunk_spans(eng, 0, widest)) == 1
                and not self._saturated())

    @staticmethod
    def _group_size(n: int, cap: int) -> int:
        """How many of a run's `n` one-window chunks still to serve go
        into the next scan group, of at most `cap` windows. Scan depths
        are compiled at powers of two, so a group is a power of two, or
        one window short of one (3 rides a depth-4 program, 7 a depth-8
        one): a padding round costs the chip one round (GIL-free) where a
        launch more costs the host a lock hold, an enqueue, a wait and a
        copy under the GIL; two padding rounds or more cost the chip more
        than the launch costs the host (PERF.md section 6, PR 44), so 5
        is 4 + 1 and 6 is 4 + 2."""
        k = min(n, cap)
        p = 1 << (k.bit_length() - 1)  # the largest power of two <= k
        return k if k == 2 * p - 1 and 2 * p <= cap else p

    def _columnar_run(self, m: int, eng, j: int, end: int, ctx: _PullCtx,
                      ws: dict) -> None:
        """Serve items [j, end) of a pull: a run of two or more chunks of
        one columnar method, each one window of the ladder (several calls
        of up to MAX_BATCH_SIZE requests pulled together: every pull of a
        busy batch front). The chunks stay the windows they are served as
        alone, cut where _handle_batch cuts them, but a group of them
        (_group_size) is ONE launch: launch_columnar_windows preps them
        in frame order under one hold of the engine lock and dispatches
        one scan program whose rounds apply in that order on the table, so
        a key that stands in two calls of the pull is decided call by
        call as lock-step decides it, under one timestamp a group;
        collect_columnar_windows waits once and copies back once. Every
        group is collected and posted before the next is launched and
        nothing is in flight when the run returns (the two pull workers
        stagger as they do lock-step; launches left in flight collide on
        the engine lock: PERF.md section 6, PR 31).

        A window with leftovers is its group's last (the engine cuts
        there): they retire through _leftover_items before the next window
        is prepped, and the rest of the run is grouped anew; where a
        group's FIRST window cuts, the run's traffic repeats keys inside a
        call and the rest goes on chunk by chunk, as it would without this
        path. An over-commit error-fills the chunk that failed, as
        lock-step does, and the run goes on behind it."""
        b = ctx.b
        mt = self._metrics
        cap = self._scan_cap(eng)
        chunks = [(c, min(c + MAX_BATCH_SIZE, end))
                  for c in range(j, end, MAX_BATCH_SIZE)]
        ci = 0
        grouping = True
        while ci < len(chunks):
            size = self._group_size(len(chunks) - ci, cap) \
                if grouping else 1
            group = chunks[ci:ci + size]
            h = None
            if size > 1:
                h = eng.launch_columnar_windows(
                    [self._col_window(b, c0, c1) for c0, c1 in group],
                    _COLUMNAR_SLOW_MASK, staging=ws["run_staging"])
            if h is None:
                # a lone chunk, or a shape the engine refused (nothing
                # mutated): today's path, chunk by chunk
                for c0, c1 in group:
                    self._chunk_alone(m, eng, c0, c1, ctx, ws)
                ci += size
                continue
            win_metas, failed = h[0], h[1]
            consumed = len(win_metas)
            self.stats["columnar_windows"] += consumed
            self.stats["columnar_groups"] += 1
            if mt is not None:
                mt.peerlink_columnar_windows.inc(consumed)
                mt.peerlink_columnar_group_windows.observe(consumed)
            if consumed:
                served = group[:consumed]
                leftovers = eng.collect_columnar_windows(
                    h, [self._col_outs(b, c0, c1) for c0, c1 in served])
                for (c0, _c1), left in zip(served, leftovers):
                    if left is not None and len(left):
                        self._leftover_items(m, c0, left.tolist(), b,
                                             ctx.errs, ctx.metas)
                self._post_span(ctx, served[0][0], served[-1][1])
            ci += consumed
            if failed is not None:
                c0, c1 = chunks[ci]  # the window whose prep over-committed
                self._col_error_fill(failed.encode(), c0, c1, b, ctx.errs)
                self._post_span(ctx, c0, c1)
                ci += 1
            elif consumed < size:
                self.stats["columnar_cuts"] += 1
                if mt is not None:
                    mt.peerlink_columnar_cuts.inc()
                grouping = consumed > 1

    def _columnar_chunk(self, m: int, eng, j: int, k: int,
                        ctx: _PullCtx, ws: dict) -> bool:
        """Serve one peer-hop chunk by itself, columnar-end-to-end (a
        run of several one-window chunks goes through _columnar_run and
        comes here only chunk by chunk: a lone chunk, the rest behind a
        first window that cut, a backend whose group is not one launch).
        A chunk that fits the engine's widest window (every chunk at the
        shipped widths: 1000 items against 8192 lanes) is one span and is
        served lock-step, posted before return. A wider chunk is
        PIPELINED: its
        sub-windows launch in scan groups of <= pipeline_scan windows
        (one device call each, models/engine.py launch_columnar_windows)
        into the WORKER-level pipeline (ws["inflight"], up to
        pipeline_depth launches), and clean groups may still be in flight
        when this chunk — and this whole pull — returns; each drained
        group's rows post immediately, so early rows ride the wire while
        later sub-windows (or the next pull's prep) ride the device.

        Per-key order still holds: deductions apply at LAUNCH time (the C
        prep packs and submits synchronously; only the readback defers),
        so dispatch order is application order across chunks and pulls —
        and a cut (leftovers: duplicates, gregorian, GLOBAL/MULTI_REGION,
        invalid) or an over-commit barriers the WHOLE shared pipeline
        before anything later dispatches. Only leftover-free groups ever
        stay in flight (tests/test_columnar_pipeline.py).
        False = the engine can't take the shape (nothing mutated; the
        caller retires the chunk via the object path and posts it)."""
        b = ctx.b
        if self._saturated():
            return False
        launch = getattr(eng, "launch_columnar_windows", None)
        spans = self._chunk_spans(eng, j, k)
        if self._col_depth <= 1 or launch is None or len(spans) <= 1:
            # lock-step serve: complete before return, post per chunk
            ok = self._columnar_chunk_lockstep(m, eng, spans, k, b,
                                               ctx.errs, ctx.metas)
            if ok:
                self._post_span(ctx, j, k)
            return ok
        mt = self._metrics
        scan = self._scan_cap(eng)
        staging = ws["staging"]
        inflight = ws["inflight"]
        wi = 0
        n_spans = len(spans)
        launched_any = False
        while wi < n_spans:
            if len(inflight) >= self._col_depth:
                # pipe full: the oldest readback gates the next launch
                self.stats["columnar_fill_stalls"] += 1
                if mt is not None:
                    mt.peerlink_columnar_fill_stalls.inc()
                if self._recorder is not None:
                    self._recorder.emit("peerlink.fill_stall",
                                        depth=self._col_depth)
                self._drain_one_entry(ws)
                continue
            gspans = spans[wi:wi + scan]
            wins = [self._col_window(b, s0, s1) for s0, s1 in gspans]
            h = launch(wins, _COLUMNAR_SLOW_MASK,
                       staging=staging[ws["seq"] % len(staging)])
            if h is None:
                if not launched_any:
                    return False  # nothing of THIS chunk mutated
                # mid-chunk refusal (defensive): earlier spans already
                # applied — barrier, then retire the rest lock-step
                self._drain_all(ws)
                rest = spans[wi:]
                if not self._columnar_chunk_lockstep(
                        m, eng, rest, k, b, ctx.errs, ctx.metas):
                    self._object_chunk(m, rest[0][0], k, b, ctx.errs,
                                       ctx.metas)
                self._post_span(ctx, rest[0][0], k)
                return True
            launched_any = True
            ws["seq"] += 1
            win_metas, failed = h[0], h[1]
            consumed = len(win_metas)
            wi += consumed
            inflight.append((eng, h, gspans[:consumed], ctx, m))
            ctx.live += 1
            self.stats["columnar_windows"] += consumed
            self.stats["columnar_groups"] += 1
            if mt is not None:
                mt.peerlink_columnar_windows.inc(consumed)
                mt.peerlink_columnar_group_windows.observe(consumed)
                mt.peerlink_columnar_occupancy.observe(len(inflight))
            cut = (consumed < len(gspans)
                   or (consumed and win_metas[-1][-1] is not None
                       and len(win_metas[-1][-1])))
            if failed is not None or cut:
                if cut and failed is None:
                    self.stats["columnar_cuts"] += 1
                    if mt is not None:
                        mt.peerlink_columnar_cuts.inc()
                    if self._recorder is not None:
                        self._recorder.emit("peerlink.columnar_cut",
                                            windows=consumed)
                # barrier: drain in dispatch order (the cut window's
                # leftovers retire inside _drain_one_entry), then resume
                failed_msg = self._drain_all(ws)
                if failed_msg is not None:
                    # over-commit: the unconsumed remainder of the chunk
                    # gets error replies (the lock-step contract)
                    s_fail = spans[wi][0] if wi < n_spans else k
                    self._col_error_fill(failed_msg.encode(), s_fail, k,
                                         b, ctx.errs)
                    self._post_span(ctx, s_fail, k)
                    return True
        return True

    def _columnar_chunk_lockstep(self, m: int, eng, spans, k: int,
                                 b: dict, errs: list, metas: list) -> bool:
        """The serial columnar path (a single-window chunk served by
        itself: a pull of one chunk, a lone request, a chunk _columnar_run
        does not group; depth 1; engines without the launch/collect split
        or whose group is a launch a window): complete sub-window i
        before submitting i+1 — the C prep's duplicate tracking is
        per-submit, so a key demoted to the leftover tail of sub-window i
        must finish before a later sub-window packs its next occurrence.
        False = the engine can't take the shape at all (nothing
        mutated)."""
        for si, (s0, s1) in enumerate(spans):
            try:
                h = eng.submit_columnar(
                    s1 - s0, b["keys"], b["key_off"][s0:s1 + 1],
                    b["name_len"][s0:s1], b["hits"][s0:s1],
                    b["limit"][s0:s1], b["duration"][s0:s1],
                    b["algorithm"][s0:s1], b["behavior"][s0:s1],
                    _COLUMNAR_SLOW_MASK)
            except Exception as e:  # noqa: BLE001 — directory over-commit
                self._col_error_fill(str(e).encode(), s0, k, b, errs)
                return True
            if h is None:
                if si == 0:
                    return False  # nothing mutated: whole-chunk fallback
                # defensive mid-stream refusal: earlier spans already
                # applied, so the remainder retires via the object path
                self._object_chunk(m, s0, k, b, errs, metas)
                return True
            leftover = eng.complete_columnar(
                h, b["status"][s0:s1], b["r_limit"][s0:s1],
                b["r_remaining"][s0:s1], b["r_reset"][s0:s1])
            if len(leftover):
                self._leftover_items(m, s0, leftover.tolist(), b, errs,
                                     metas)
        return True

    def _leftover_items(self, m: int, j: int, rel_idx: List[int], b: dict,
                        errs: list, metas: list) -> None:
        """Request-object tail of a columnar chunk: the lanes the C prep
        demoted (invalid, gregorian, GLOBAL/MULTI_REGION, duplicates).
        Runs AFTER the packed round, preserving per-key order. Method 0
        (public) leftovers take the FULL router path — a GLOBAL-flagged
        request on the wire-compatible surface must reach the global
        pipelines, not owner-apply semantics.

        Metered as stats `leftover_items` (peerlink_leftover_items_total)
        and the profiler's `leftover` phase, a host span of that name
        while a capture runs, with three children: `leftover.build` (the
        request objects), `leftover.serve` (the instance's call: the wait
        for the combiner and the engine's rounds) and `leftover.fill` (the
        answers into the pull's rows; docs/observability.md). The C prep
        gives no reason for a demotion, so the items are counted whole."""
        n_left = len(rel_idx)  # a local: no call between read and store
        self.stats["leftover_items"] += n_left
        prof = self._prof
        t0 = time.perf_counter_ns()
        with prof.span("leftover"):
            sub = prof.seams()
            sub("leftover.build")
            idxs = [j + r for r in rel_idx]
            reqs, good_idx = [], []
            koff = b["key_off"]
            nlen = b["name_len"]
            raw_keys = b["keys"]
            for i in idxs:
                lo, hi = int(koff[i]), int(koff[i + 1])
                split = lo + int(nlen[i])
                try:
                    reqs.append(RateLimitReq(
                        name=raw_keys[lo:split].decode(),
                        unique_key=raw_keys[split:hi].decode(),
                        hits=int(b["hits"][i]), limit=int(b["limit"][i]),
                        duration=int(b["duration"][i]),
                        algorithm=int(b["algorithm"][i]),
                        behavior=int(b["behavior"][i])))
                    good_idx.append(i)
                except UnicodeDecodeError:
                    self._fill_one(b, i, RateLimitResp(
                        error="invalid utf-8 in key"), errs, metas)
            if reqs:
                sub("leftover.serve")
                try:
                    if m == METHOD_GET_PEER_RATE_LIMITS:
                        resps = self.instance.apply_owner_batch_direct(
                            reqs, from_peer_rpc=True)
                    else:
                        resps = self.instance.get_rate_limits(reqs)
                except Exception as e:  # noqa: BLE001
                    resps = [RateLimitResp(error=str(e)) for _ in reqs]
                sub("leftover.fill")
                for i, resp in zip(good_idx, resps):
                    self._fill_one(b, i, resp, errs, metas)
            sub(None)
        prof.observe_leftover(time.perf_counter_ns() - t0)

    @staticmethod
    def _fill_one(b: dict, i: int, resp: RateLimitResp, errs: list,
                  metas: Optional[list] = None) -> None:
        b["status"][i] = int(resp.status)
        b["r_limit"][i] = resp.limit
        b["r_remaining"][i] = resp.remaining
        b["r_reset"][i] = resp.reset_time
        if resp.error:
            errs.append((i, resp.error.encode()))
        if metas is not None and resp.metadata:
            metas.append((i, _encode_pb_metadata(resp.metadata)))

    def _carrier_chunk(self, m: int, j: int, k: int, b: dict,
                       errs: list, metas: list) -> None:
        """A run of flagged (traced/deadlined) items: split at frame
        boundaries (rid/conn change — the aggregated pull may have merged
        several flagged frames) and handle each with its own contexts."""
        rid, conn = b["rid"], b["conn"]
        i = j
        while i < k:
            e = i + 1
            while e < k and rid[e] == rid[i] and conn[e] == conn[i]:
                e += 1
            # the carriers lead THEIR FRAME; a frame continued from a
            # previous (batch-cap-split) chunk carries no new context
            frame_start = i == 0 or rid[i] != rid[i - 1] \
                or conn[i] != conn[i - 1]
            self._carrier_frame(m, i, e, b, errs, metas, frame_start)
            i = e

    def _carrier_item(self, b: dict, i: int) -> str:
        """A carrier item's unique_key field, decoded ("" for garbage —
        the link port is unauthenticated, so a crafted carrier degrades
        to context-less serving, never a worker death)."""
        lo, hi = int(b["key_off"][i]), int(b["key_off"][i + 1])
        split = lo + int(b["name_len"][i])
        try:
            return b["keys"][split:hi].decode()
        except UnicodeDecodeError:
            return ""

    def _carrier_frame(self, m: int, i: int, e: int, b: dict, errs: list,
                       metas: list, frame_start: bool) -> None:
        from gubernator_tpu.obs import trace
        from gubernator_tpu.service import deadline as deadline_mod

        base = m & ~METHOD_FLAGS
        span = None
        dl = None
        lease_lane = -1
        lease_key = ""
        start = i
        if frame_start:
            if m & METHOD_TRACED and start < e:
                tracer = getattr(self.instance, "tracer", None)
                if tracer is not None:
                    span = tracer.continue_trace(
                        "owner.apply", self._carrier_item(b, start))
                if span is not None:
                    span.set("transport", "peerlink")
                self._fill_one(b, start, RateLimitResp(), errs, metas)
                start += 1
            if m & METHOD_DEADLINE and start < e:
                try:
                    budget_ms = float(self._carrier_item(b, start))
                except ValueError:
                    budget_ms = 0.0
                dl = deadline_mod.capture(budget_ms)
                if dl is not None:
                    note = getattr(self.instance, "observe_budget", None)
                    if note is not None:
                        note("peer", budget_ms)
                self._fill_one(b, start, RateLimitResp(), errs, metas)
                start += 1
            if m & METHOD_LEASE and start < e:
                lease_lane = start
                lease_key = self._carrier_item(b, start)
                # pre-fill the no-grant shape NOW (response buffers are
                # reused across batches — every lane must be written even
                # when the frame turns out to be carriers-only); the real
                # grant overwrites it after the chunk is handled
                b["status"][lease_lane] = -1
                b["r_limit"][lease_lane] = 0
                b["r_remaining"][lease_lane] = 0
                b["r_reset"][lease_lane] = 0
                start += 1
        if start >= e:
            return
        token = trace.use(span)
        dtoken = deadline_mod.use(dl)
        try:
            # via the combiner (direct=False): a traced window's
            # enqueue->launch wait is exactly the phase a sampled request
            # exists to measure, and a budgeted window's queue wait is
            # where the combiner's dequeue-time shed can catch it
            self._object_chunk(base, start, e, b, errs, metas,
                               direct=span is None and dl is None)
        finally:
            deadline_mod.reset(dtoken)
            trace.reset(token)
            if span is not None:
                self.instance.tracer.finish(span)
        if lease_lane >= 0 and base == METHOD_GET_PEER_RATE_LIMITS:
            self._fill_lease_lane(b, lease_lane, start, e, lease_key)

    def _fill_lease_lane(self, b: dict, lane: int, j: int, k: int,
                         key: str) -> None:
        """Answer a METHOD_LEASE ask: find the asked key's LAST occurrence
        among the frame's handled items [j, k) — its response columns
        reflect the whole frame's deductions — and overwrite the carrier's
        response lane with the owner's grant (encoding documented at
        METHOD_LEASE). The lane keeps its pre-filled no-grant shape when
        the key is absent, cold, throttled, or shed."""
        lm = getattr(self.instance, "leases", None)
        if lm is None or not lm.enabled or not key:
            return
        koff, nlen, raw = b["key_off"], b["name_len"], b["keys"]
        for i in range(k - 1, j - 1, -1):
            lo, hi = int(koff[i]), int(koff[i + 1])
            split = lo + int(nlen[i])
            try:
                if raw[lo:split].decode() + "_" + raw[split:hi].decode() \
                        != key:
                    continue
            except UnicodeDecodeError:
                continue
            g = lm.grant(key, int(b["r_remaining"][i]),
                         int(b["r_reset"][i]))
            if g is not None:
                b["status"][lane] = i - j
                b["r_limit"][lane] = g[0]
                b["r_remaining"][lane] = g[1]
                b["r_reset"][lane] = g[2]
            return

    def _object_chunk(self, m: int, j: int, k: int, b: dict,
                      errs: list, metas: list,
                      direct: bool = True) -> None:
        """The request-object path (non-peer-hop methods, or no columnar
        backend): decode -> one handler call -> fill. `direct=False`
        routes peer-hop chunks through the combiner instead of
        apply_owner_batch_direct (traced frames: the batch-window wait is
        part of the measured phases)."""
        koff = b["key_off"][j:k + 1].tolist()
        nlen = b["name_len"][j:k].tolist()
        hits = b["hits"][j:k].tolist()
        limit = b["limit"][j:k].tolist()
        duration = b["duration"][j:k].tolist()
        algorithm = b["algorithm"][j:k].tolist()
        behavior = b["behavior"][j:k].tolist()
        raw_keys = b["keys"]
        # None marks an item whose wire bytes are invalid (the link port is
        # unauthenticated: one crafted non-UTF-8 key must produce a
        # per-item error reply, never kill the whole aggregated pull)
        reqs: List[Optional[RateLimitReq]] = []
        for o in range(k - j):
            lo, hi = koff[o], koff[o + 1]
            split = lo + nlen[o]
            try:
                reqs.append(RateLimitReq(
                    name=raw_keys[lo:split].decode(),
                    unique_key=raw_keys[split:hi].decode(), hits=hits[o],
                    limit=limit[o], duration=duration[o],
                    algorithm=algorithm[o], behavior=behavior[o]))
            except UnicodeDecodeError:
                reqs.append(None)
        good = [r for r in reqs if r is not None]
        try:
            if not good:
                handled = []
            elif m == METHOD_GET_PEER_RATE_LIMITS and direct:
                # this worker's pull IS the batch window: go straight to
                # the backend (owner semantics preserved; combiner hop
                # saved — see Instance.apply_owner_batch_direct)
                handled = self.instance.apply_owner_batch_direct(
                    good, from_peer_rpc=True)
            elif m == METHOD_GET_PEER_RATE_LIMITS:
                handled = self.instance.apply_owner_batch(
                    good, from_peer_rpc=True)
            elif m == METHOD_GET_RATE_LIMITS:
                handled = self.instance.get_rate_limits(good)
            else:
                # unknown method byte (the C parser accepts any non-control
                # value structurally): answer UNIMPLEMENTED per item — never
                # serve a decision under a contract we don't speak, never
                # strand the rid
                handled = [RateLimitResp(
                    error=f"unimplemented wire method 0x{m:02x}")
                    for _ in good]
        except Exception as e:  # noqa: BLE001 — per-item error replies
            handled = [RateLimitResp(error=str(e)) for _ in good]
        if len(good) == len(reqs):
            resps = handled
        else:  # scatter handler results back around the bad items
            it = iter(handled)
            resps = [RateLimitResp(error="invalid utf-8 in key")
                     if r is None else next(it) for r in reqs]
        for o, resp in enumerate(resps):
            self._fill_one(b, j + o, resp, errs, metas)

"""Wire contract v2 acceptance (ISSUE 8): sequence-numbered partial
responses + cross-pull pipelining on the peerlink.

The bar, in the issue's words: v2 responses are BIT-IDENTICAL in content
to the lock-step engine-level reference (per-key order preserved across
partial posts); a client that never says HELLO is served byte-exact v1
whole frames by the one worker; negotiation survives reconnects; mixed
v1/v2 fleets interop across forwards, GLOBAL drains, lease carriers, and
deadline/trace carrier flags; and a mid-stream disconnect drops partial
reassembly on both ends without leaking pending entries.

An old binary on the wire is `wire_v2=False` on the constructors: a
server that never greets (handed to pls_start2), a client that ignores
the greeting. It selects no serving code and nothing reads it from the
environment or from BehaviorConfig.
"""

import socket
import struct
import threading
import time

import numpy as np
import pytest

from gubernator_tpu.cluster.harness import LocalCluster, wire_peerlink
from gubernator_tpu.service import deadline as deadline_mod
from gubernator_tpu.service.peer_client import PeerClient
from gubernator_tpu.service.peerlink import (
    METHOD_GET_PEER_RATE_LIMITS,
    PeerLinkClient,
    PeerLinkError,
    WIRE_PARTIAL,
    encode_request_frame,
)
from gubernator_tpu.types import Behavior, PeerInfo, RateLimitReq, Status

from test_columnar_pipeline import (
    NOW,
    _engine,
    _random_reqs,
    _rows,
    _serve,
    assert_served_rows,
    pin_engine_clock,
    reference_rows,
)


def _req(key, hits=1, limit=10, behavior=0, name="w2"):
    return RateLimitReq(name=name, unique_key=key, hits=hits, limit=limit,
                        duration=60_000, behavior=behavior)


def _close_all(*objs):
    for o in objs:
        o.close()


# --------------------------------------------------------------- negotiate


class TestNegotiation:
    def test_v2_negotiates_and_streams_partials(self):
        """Default build: client upgrades to v2 and wide pulls leave as
        partial frames; nothing pends once the wire is quiet."""
        ip, sp, cp = _serve(_engine(), pipeline_depth=3, pipeline_scan=4)
        cli = PeerLinkClient(f"127.0.0.1:{sp.port}")
        try:
            for it in range(8):
                reqs = [_req(f"neg{it}_{i}", limit=1000) for i in range(96)]
                out = cli.call(METHOD_GET_PEER_RATE_LIMITS, reqs, 30.0)
                assert all(r.error == "" for r in out)
            assert cli.wire_version == 2
            assert sp.wire_partial_posts() > 0
            assert sp.wire_debug()["v2_conns"] >= 1
            deadline = time.time() + 5
            while sp.wire_pending_count() and time.time() < deadline:
                time.sleep(0.01)
            assert sp.wire_pending_count() == 0
            assert cli.partial_state_count() == 0
        finally:
            _close_all(cli, cp, sp, ip)

    def test_v1_pinned_client_never_upgrades(self):
        """wire_v2=False on the client: it ignores the greeting, never
        HELLOs, and the server answers it whole-frame only."""
        ip, sp, cp = _serve(_engine())
        cli = PeerLinkClient(f"127.0.0.1:{sp.port}", wire_v2=False)
        try:
            before = sp.wire_partial_posts()
            for i in range(4):
                reqs = [_req(f"pin{i}_{j}", limit=500) for j in range(64)]
                out = cli.call(METHOD_GET_PEER_RATE_LIMITS, reqs, 30.0)
                assert all(r.error == "" for r in out)
            assert cli.wire_version == 1
            # partial frames only ever leave toward upgraded conns
            assert sp.wire_partial_posts() == before
        finally:
            _close_all(cli, cp, sp, ip)

    def test_rid_parsed_before_hello_stays_whole_frame(self):
        """The HELLO races the client's first request frames (the client
        pipelines without waiting on the greeting round-trip), so a rid
        can be parsed while the conn is still v1 and COMPLETE after the
        upgrade. The server latches the version per rid at parse time
        (C++ PendingReply.wire_v2): a pre-HELLO rid must come back as
        ONE whole v1 frame, a post-HELLO rid as partial frames.
        Branching on the conn's CURRENT version at post time instead
        streamed only the post-upgrade spans of a half-accumulated rid —
        the client's reassembly ended with holes and the link died
        (caught live by the wire bench)."""
        ip, sp, cp = _serve(_engine(), pipeline_depth=3, pipeline_scan=4)
        try:
            with socket.create_connection(
                    ("127.0.0.1", sp.port), 5.0) as s:
                s.settimeout(30.0)
                buf = b""
                def read_frame():
                    nonlocal buf
                    while True:
                        if len(buf) >= 4:
                            (ln,) = struct.unpack_from("<I", buf, 0)
                            if len(buf) >= 4 + ln:
                                payload = buf[4:4 + ln]
                                buf = buf[4 + ln:]
                                return payload
                        chunk = s.recv(65536)
                        assert chunk, "server closed the conn"
                        buf += chunk
                g = read_frame()  # the greeting
                assert g[8] == 0xF0
                # ONE write: rid 1, then HELLO, then rid 2 — the server
                # parses in order, so rid 1 lands pre-upgrade and rid 2
                # post-upgrade, while rid 1's rows finalize after the
                # conn has already flipped to v2
                f1 = encode_request_frame(
                    1, METHOD_GET_PEER_RATE_LIMITS,
                    [_req(f"pre{i}", limit=1000) for i in range(96)])
                hello = struct.pack("<IQBH", 11, 0, 0xF1, 2)
                f2 = encode_request_frame(
                    2, METHOD_GET_PEER_RATE_LIMITS,
                    [_req(f"post{i}", limit=1000) for i in range(96)])
                s.sendall(f1 + hello + f2)
                methods = {1: set(), 2: set()}
                covered = {1: 0, 2: 0}
                while covered[1] < 96 or covered[2] < 96:
                    p = read_frame()
                    (rid,) = struct.unpack_from("<Q", p, 0)
                    m = p[8]
                    (count,) = struct.unpack_from("<H", p, 9)
                    assert rid in (1, 2), (rid, m)
                    methods[rid].add(m)
                    covered[rid] += count
            # pre-HELLO rid: exactly one whole v1 reply, never partials
            assert methods[1] == {METHOD_GET_PEER_RATE_LIMITS}
            # post-HELLO rid: streamed as partial frames only
            assert methods[2] == {WIRE_PARTIAL}
        finally:
            _close_all(cp, sp, ip)

    def test_negotiation_survives_reconnect(self):
        """Close + reconnect re-runs the handshake from scratch — the
        upgrade is per-connection state, not per-peer memory."""
        ip, sp, cp = _serve(_engine())
        try:
            for _ in range(3):
                cli = PeerLinkClient(f"127.0.0.1:{sp.port}")
                out = cli.call(METHOD_GET_PEER_RATE_LIMITS,
                               [_req("rc", limit=10_000)], 30.0)
                assert out[0].error == ""
                assert cli.wire_version == 2
                cli.close()
                assert cli.partial_state_count() == 0
        finally:
            _close_all(cp, sp, ip)


def _resp_frame(rid, method, rows):
    """A v1 whole reply frame as docs/wire.md lays it out, error-free:
    length, rid, method, count, then the status/limit/remaining/reset
    columns and a zero error-length column."""
    n = len(rows)
    st, li, re, rs = zip(*rows)
    body = (struct.pack("<QBH", rid, method, n)
            + struct.pack(f"<{n}i", *st) + struct.pack(f"<{n}q", *li)
            + struct.pack(f"<{n}q", *re) + struct.pack(f"<{n}q", *rs)
            + struct.pack(f"<{n}H", *([0] * n)))
    return struct.pack("<I", len(body)) + body


class TestOldClientWholeFrames:
    """A peer that never says HELLO is served whole v1 frames by the one
    worker (C++ accumulates the posted spans per rid), byte for byte."""

    def _collect_frames(self, port, reqs_rounds, settle_s=0.3):
        """Send each round as one v1 frame; return every frame received
        (control frames included), raw, in arrival order — reading until
        every request's reply (method < 0xF0) has arrived plus a short
        settle window for any trailing control traffic."""
        frames = []
        replies = 0
        with socket.create_connection(("127.0.0.1", port), 5.0) as s:
            s.settimeout(30.0)
            buf = b""
            want = 0
            for rid, reqs in enumerate(reqs_rounds, start=1):
                s.sendall(encode_request_frame(
                    rid, METHOD_GET_PEER_RATE_LIMITS, reqs))
                want += 1
            deadline = time.time() + 30
            while replies < want and time.time() < deadline:
                if len(buf) >= 4:
                    (length,) = struct.unpack_from("<I", buf, 0)
                    if len(buf) - 4 >= length:
                        frames.append(bytes(buf[:4 + length]))
                        (method,) = struct.unpack_from("<B", buf, 4 + 8)
                        if method < 0xF0:
                            replies += 1
                        buf = buf[4 + length:]
                        continue
                buf += s.recv(65536)
            s.settimeout(settle_s)
            try:
                extra = s.recv(65536)
                if extra:
                    frames.append(extra)
            except socket.timeout:
                pass
        return frames

    @pytest.mark.parametrize("max_width", [16, 64])
    def test_silent_client_gets_byte_exact_v1_frames(self, monkeypatch,
                                                     max_width):
        """Three frames pipelined on one connection that never answers
        the greeting: the stream is the greeting, then exactly one whole
        frame per request, each byte for byte the encoding of what the
        engine-level reference (run_lockstep on a twin engine, the clock
        pinned) says — no partial frame, no other control frame. Frames
        two and three revisit keys of the first and the third is wider
        than the engine's widest window at 16, so its rows reach C++ in
        several posts and still leave as one frame; at 64 each frame fits
        one window and is served lock-step inside its pull (the one-item
        frame, pulled alone, is the lone request).

        One batch worker: with two, whichever pulls first decides first
        and the `remaining` column moves between runs."""
        rounds = [[_req(f"bx{i}", limit=100) for i in range(24)],
                  [_req("bx0", hits=2, limit=100)],
                  [_req(f"bx{i % 5}", limit=100) for i in range(40)]]
        pin_engine_clock(monkeypatch)
        ip, sp, cp = _serve(_engine(max_width), pipeline_depth=3,
                            pipeline_scan=4, workers=1)
        twin = _engine(max_width)
        try:
            got = self._collect_frames(sp.port, rounds)
            _rid0, m0, _c0 = struct.unpack_from("<QBH", got[0], 4)
            assert m0 == 0xF0  # the greeting
            replies = got[1:]
            assert len(replies) == len(rounds)
            for rid, (frame, reqs) in enumerate(zip(replies, rounds),
                                                start=1):
                want = _resp_frame(rid, METHOD_GET_PEER_RATE_LIMITS,
                                   reference_rows(twin, reqs, NOW))
                assert frame == want, (rid, frame.hex(), want.hex())
            assert sp.wire_partial_posts() == 0
        finally:
            _close_all(cp, sp, ip)

    def test_old_and_new_client_receive_the_same_rows(self, monkeypatch):
        """An old client (ignores the greeting) and a new one (upgrades)
        send the same requests to twin engines behind the one worker:
        the rows are the same, column for column, although one side got
        them as whole frames and the other as partial frames."""
        clock = pin_engine_clock(monkeypatch)
        io, so, co = _serve(_engine(), pipeline_depth=3, pipeline_scan=4)
        inw, sn, cn = _serve(_engine(), pipeline_depth=3, pipeline_scan=4)
        old = PeerLinkClient(f"127.0.0.1:{so.port}", wire_v2=False)
        new = PeerLinkClient(f"127.0.0.1:{sn.port}")
        rng = np.random.default_rng(30)
        try:
            new.call(METHOD_GET_PEER_RATE_LIMITS, [_req("warm")], 30.0)
            for it in range(4):
                clock["now"] = NOW + it * 700
                reqs = _random_reqs(rng, int(rng.integers(40, 150)),
                                    n_keys=18)
                a = old.call(METHOD_GET_PEER_RATE_LIMITS, reqs, 30.0)
                b = new.call(METHOD_GET_PEER_RATE_LIMITS, reqs, 30.0)
                assert [r.error for r in a] == [r.error for r in b]
                assert _rows(a) == _rows(b), it
            assert old.wire_version == 1 and new.wire_version == 2
            assert so.wire_partial_posts() == 0
            assert sn.wire_partial_posts() > 0
        finally:
            _close_all(old, new, co, cn, so, sn, io, inw)


# ------------------------------------------------------------ differential


class TestDifferentialV2:
    @pytest.mark.parametrize("max_width", [16, 256])
    def test_v2_contents_bit_identical_to_lockstep(self, monkeypatch,
                                                   max_width):
        """The acceptance hammer: duplicate keys, gregorian, invalid and
        GLOBAL leftover cuts through a full v2 link (partial posts +
        cross-pull pipelining) against the lock-step engine-level
        reference (run_lockstep on a twin engine) — every column must
        match item-for-item, reset_time and leaky buckets included: the
        clock is pinned, so there is no second service whose wall clock
        could land a leak tick away. At width 256 every frame is one
        window, served lock-step with its leftovers behind it."""
        clock = pin_engine_clock(monkeypatch)
        ip, sp, cp = _serve(_engine(max_width), pipeline_depth=3,
                            pipeline_scan=4)
        twin = _engine(max_width)
        c2 = PeerLinkClient(f"127.0.0.1:{sp.port}")
        rng = np.random.default_rng(88)
        try:
            c2.call(METHOD_GET_PEER_RATE_LIMITS, [_req("warm")], 30.0)
            for it in range(6):
                clock["now"] = NOW + it * 500
                reqs = _random_reqs(rng, int(rng.integers(40, 150)),
                                    n_keys=18)
                reqs[int(rng.integers(0, len(reqs)))] = RateLimitReq(
                    name="cp", unique_key=f"gl{it}", hits=1, limit=9,
                    duration=60_000, behavior=int(Behavior.GLOBAL))
                got = c2.call(METHOD_GET_PEER_RATE_LIMITS, reqs, 30.0)
                assert_served_rows(
                    got, reference_rows(twin, reqs, clock["now"]), reqs, it)
            assert c2.wire_version == 2
            assert sp.wire_partial_posts() > 0  # v2 actually streamed
        finally:
            _close_all(c2, cp, sp, ip)

    def test_duplicate_key_order_across_partial_posts(self):
        """One frame hammering ONE key: hits must apply in item order no
        matter how the rows leave as partial frames — the remaining
        column must be the exact arithmetic sequence."""
        ip, sp, cp = _serve(_engine(), pipeline_depth=3, pipeline_scan=4)
        cli = PeerLinkClient(f"127.0.0.1:{sp.port}")
        try:
            n = 120
            out = cli.call(METHOD_GET_PEER_RATE_LIMITS,
                           [_req("dup", hits=1, limit=n) for _ in range(n)],
                           30.0)
            for i, r in enumerate(out):
                assert r.error == "" and r.remaining == n - 1 - i, (i, r)
        finally:
            _close_all(cli, cp, sp, ip)


# --------------------------------------------------------- drains and leaks


class TestDrainsAndLeaks:
    @pytest.mark.parametrize("max_width", [16, 64])
    def test_clean_drain_on_close_v2(self, max_width):
        """Close racing live v2 traffic: every caller completes or gets
        PeerLinkError — never a hang — and neither side leaks partial
        state. At width 64 every frame is one window, served lock-step
        inside its pull."""
        eng = _engine(max_width)
        ip, sp, cp = _serve(eng, pipeline_depth=3, pipeline_scan=4)
        cli = PeerLinkClient(f"127.0.0.1:{sp.port}")
        errs, done = [], []

        def caller(i):
            reqs = [_req(f"dr{i}_{j}", limit=50) for j in range(64)]
            try:
                done.append(cli.call(METHOD_GET_PEER_RATE_LIMITS, reqs,
                                     10.0))
            except PeerLinkError:
                done.append(None)
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=caller, args=(i,), daemon=True)
              for i in range(6)]
        for t in ts:
            t.start()
        sp.close()  # races the calls deliberately
        for t in ts:
            t.join(timeout=20)
        assert not any(t.is_alive() for t in ts)
        assert not errs
        assert cli.partial_state_count() == 0
        _close_all(cli, cp, ip)

    def test_midstream_server_death_drops_partial_reassembly(self):
        """The server dies between partial frames: in-flight futures fail
        with PeerLinkError (never hang) and the client's reassembly map
        is empty afterwards — the leak probe of the issue's acceptance."""
        ip, sp, cp = _serve(_engine(), pipeline_depth=3, pipeline_scan=4)
        cli = PeerLinkClient(f"127.0.0.1:{sp.port}")
        futs = []
        try:
            for i in range(8):
                futs.append(cli.call_async(
                    METHOD_GET_PEER_RATE_LIMITS,
                    [_req(f"ms{i}_{j}", limit=50) for j in range(96)])[0])
        finally:
            sp.close()
        for f in futs:
            try:
                f.result(timeout=20)
            except Exception:  # noqa: BLE001 — failing loudly is the point
                pass
        assert cli.partial_state_count() == 0
        _close_all(cli, cp, ip)

    def test_client_vanish_reaps_server_pending(self):
        """A client that disconnects mid-pull must not leave pending
        reply entries behind on the server (conn teardown reaps them)."""
        ip, sp, cp = _serve(_engine(), pipeline_depth=3, pipeline_scan=4)
        try:
            s = socket.create_connection(("127.0.0.1", sp.port), 5.0)
            s.sendall(encode_request_frame(
                7, METHOD_GET_PEER_RATE_LIMITS,
                [_req(f"cv{j}", limit=50) for j in range(96)]))
            s.close()  # gone before (or while) the reply streams
            deadline = time.time() + 10
            while sp.wire_pending_count() and time.time() < deadline:
                time.sleep(0.02)
            assert sp.wire_pending_count() == 0
        finally:
            _close_all(cp, sp, ip)


# ------------------------------------------------------------ mixed fleet


@pytest.mark.chaos
class TestMixedVersionCluster:
    """A rolling upgrade in miniature: node 0 speaks v2, node 1 is an
    old binary on the wire, built through the constructors' `wire_v2=False`
    (its server never greets, its outbound links ignore the greeting).
    Everything that rides the link must interop in BOTH directions."""

    @staticmethod
    def _old_link(pc):
        """Give a PeerClient of the old node the link an old binary would
        dial: a PeerLinkClient that never answers the greeting."""
        host, _, port = pc.info.address.rpartition(":")
        pc._link = PeerLinkClient(
            f"{host}:{int(port) + pc.conf.peer_link_offset}",
            fault_key=pc.info.address, wire_v2=False)
        return pc

    def _mixed(self):
        c = LocalCluster().start(2)
        links = wire_peerlink(c, old_nodes=(1,))
        if not links:
            c.stop()
            pytest.skip("no free peerlink port offset on this host")
        old = c.instances[1]
        for p in old.instance.all_peer_clients():
            if p.info.address != old.address:
                self._old_link(p)
        return c, links

    def _key_owned_by(self, sender, owner_ci, prefix, name="w2"):
        # digit-first keys (the test_peerlink idiom): crc32 clusters a
        # shared prefix with a trailing counter into a few ring arcs, so
        # `g_0..g_N` can all land on one node; varying the first byte
        # spreads the scan across the ring
        for i in range(256):
            k = f"{i}{prefix}"
            peer = sender.instance.get_peer(
                _req(k, name=name).hash_key())
            if peer.info.address == owner_ci.address:
                return k
        raise AssertionError("no key landed on the target owner")

    def test_forwards_global_leases_and_carriers_interop(self):
        c, links = self._mixed()
        v2node, v1node = c.instances
        try:
            # ---- forwards, both directions -------------------------------
            k01 = self._key_owned_by(v2node, v1node, "f01_")
            r = v2node.instance.get_rate_limits([_req(k01)])[0]
            assert r.error == "" and r.remaining == 9
            k10 = self._key_owned_by(v1node, v2node, "f10_")
            r = v1node.instance.get_rate_limits([_req(k10)])[0]
            assert r.error == "" and r.remaining == 9
            # the v2->v1 link negotiated down to whole-frame; the v1-pinned
            # node never upgrades its own outbound link either
            deadline = time.time() + 5
            while time.time() < deadline:
                vers = {p.info.address: p.link_wire_version()
                        for ci in c.instances
                        for p in ci.instance.all_peer_clients()
                        if p.info.address != ci.address
                        and hasattr(p, "link_wire_version")}
                if vers and all(v == 1 for v in vers.values()):
                    break
                time.sleep(0.05)
            assert vers and all(v == 1 for v in vers.values()), vers

            # ---- GLOBAL drains across the mixed pair ---------------------
            gk = self._key_owned_by(v1node, v2node, "g_", name="w2")
            greq = _req(gk, hits=5, limit=100,
                        behavior=int(Behavior.GLOBAL))
            r = v1node.instance.get_rate_limits([greq])[0]
            assert r.status == Status.UNDER_LIMIT
            peek = _req(gk, hits=0, limit=100,
                        behavior=int(Behavior.GLOBAL))
            deadline = time.time() + 10
            owner_sees = -1
            while time.time() < deadline:
                owner_sees = v2node.instance.get_rate_limits(
                    [peek])[0].remaining
                if owner_sees == 95:
                    break
                time.sleep(0.05)
            assert owner_sees == 95

            # ---- deadline carrier (METHOD_DEADLINE flag) both ways -------
            for src, dst in ((v2node, v1node), (v1node, v2node)):
                pc = PeerClient(src.instance.conf.behaviors,
                                PeerInfo(address=dst.address))
                if src is v1node:
                    self._old_link(pc)
                try:
                    dst.instance.last_budget_ms.pop("peer", None)
                    dl = deadline_mod.capture(800)
                    time.sleep(0.005)
                    r = pc.get_peer_rate_limits(
                        [_req(f"dl_{dst.address}", limit=100)],
                        deadline=dl)[0]
                    assert r.error == ""
                    hop = dst.instance.last_budget_ms["peer"]
                    assert 0 < hop < 800, hop
                finally:
                    pc.shutdown(timeout_s=2)

            # ---- trace carrier (METHOD_TRACED flag) v2 -> v1 -------------
            from gubernator_tpu.obs.trace import Span

            v1node.instance.tracer.sample = 1.0
            span = Span("ab" * 16, "cd" * 8, "", "test.root",
                        time.time_ns())
            pc = PeerClient(v2node.instance.conf.behaviors,
                            PeerInfo(address=v1node.address))
            try:
                r = pc.get_peer_rate_limits([_req("tr", limit=100)],
                                            trace_span=span)[0]
                assert r.error == ""
                owner_spans = v1node.instance.tracer.traces(
                    "ab" * 16).get("ab" * 16, [])
                assert owner_spans, "trace context did not cross the wire"
            finally:
                pc.shutdown(timeout_s=2)

            # ---- lease carrier (METHOD_LEASE flag) over the mixed link ---
            for ci in c.instances:
                b = ci.instance.conf.behaviors
                b.hot_leases = True
                b.hot_lease_rate = 20.0
                b.hot_lease_window_s = 0.1
                b.hot_lease_ttl_s = 2.0
                b.hot_lease_fraction = 0.5
                ci.instance.leases.arm()
            lk = self._key_owned_by(v1node, v2node, "ls_", name="lease")
            lreq = RateLimitReq(name="lease", unique_key=lk, hits=1,
                                limit=1000, duration=60_000)
            from gubernator_tpu.service.leases import LEASED_METADATA_KEY

            leased = 0
            for _ in range(200):
                r = v1node.instance.get_rate_limits([lreq])[0]
                assert r.error == ""
                if r.metadata.get(LEASED_METADATA_KEY):
                    leased += 1
                time.sleep(0.002)
            assert v2node.instance.leases.stats["grants"] >= 1 or leased
        finally:
            for svc in links:
                svc.close()
            c.stop()

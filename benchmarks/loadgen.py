"""One load-generator process: a few callers, each with its own connection
and its own seeded pool of pre-serialised calls. A mix's `loop` says which
kind: `closed` (the next call when the last is back, timed from its send)
or `open` (each call at the instant the seeded schedule makes it due,
whether or not earlier ones are back, timed from that due instant).

Imports nothing of the program and no JAX. Inside the run it only sends,
receives and appends; responses are decoded and checked for shape after
the window, and the answers for audited keys go back to the parent with
the send and receive instants of their calls.
"""

from __future__ import annotations

import functools
import threading
import time
import traceback

import numpy as np

from traffic import Traffic
from wire import GRPC_METHOD, decode_responses


def _caller(address, pool, timeout_s, start_wall, end_wall, out, errors):
    """Closed loop: the next call goes out when the previous one is back.
    Appends (pool index, send wall ns, recv wall ns, latency ns, bytes or
    error string) per call."""
    try:
        _call_loop(address, pool, timeout_s, start_wall, end_wall, out)
    except Exception:  # noqa: BLE001 — re-raised by main() after the join
        errors.append(traceback.format_exc())


def _call_loop(address, pool, timeout_s, start_wall, end_wall, out):
    import grpc

    # one TCP connection per caller, as separate application servers have
    channel = grpc.insecure_channel(
        address, options=[("grpc.use_local_subchannel_pool", 1)])
    call = channel.unary_unary(GRPC_METHOD, request_serializer=None,
                               response_deserializer=None)
    try:
        grpc.channel_ready_future(channel).result(timeout=30)
        while time.time() < start_wall:
            time.sleep(0.001)
        i, n = 0, len(pool)
        end_ns = int(end_wall * 1e9)
        while True:
            w0 = time.time_ns()
            if w0 >= end_ns:
                break
            m0 = time.perf_counter_ns()
            try:
                got = call(pool[i % n].body, timeout=timeout_s)
            except grpc.RpcError as e:
                got = f"rpc error: {e.code()}"
            m1 = time.perf_counter_ns()
            out.append((i % n, w0, time.time_ns(), m1 - m0, got))
            i += 1
    finally:
        channel.close()


UNANSWERED = "unanswered"  # its deadline passed, or it was cancelled at the end


class _Clock:
    """The process's monotonic clock, anchored once to the wall clock: every
    instant of an open loop is read from it, so a due instant, a send and a
    receipt differ by what passed and by nothing the wall clock did."""

    def __init__(self):
        self._wall0, self._mono0 = time.time_ns(), time.perf_counter_ns()

    def now_ns(self) -> int:
        return self._wall0 + time.perf_counter_ns() - self._mono0


def _due_ns(traffic, client, start_wall, end_wall) -> np.ndarray:
    """Wall ns at which the client's calls are due in [start, end)."""
    span_ns, n = int((end_wall - start_wall) * 1e9), 1024
    while True:
        offsets = traffic.arrival_offsets_ns(client, n)
        if offsets[-1] >= span_ns:
            return int(start_wall * 1e9) + offsets[offsets < span_ns]
        n *= 2


def _sender(address, pool, due_ns, timeout_s, clock, out, errors):
    """Open loop: call k goes out when it is due, whether or not call k-1
    is back. Appends (pool index, send ns, recv ns, latency ns from the due
    instant, bytes or error string, due ns) per call, in the order due."""
    try:
        _open_loop(address, pool, due_ns, timeout_s, clock, out)
    except Exception:  # noqa: BLE001 — re-raised by main() after the join
        errors.append(traceback.format_exc())


def _open_loop(address, pool, due_ns, timeout_s, clock, out):
    import grpc

    channel = grpc.insecure_channel(
        address, options=[("grpc.use_local_subchannel_pool", 1)])
    call = channel.unary_unary(GRPC_METHOD, request_serializer=None,
                               response_deserializer=None)
    flight = threading.Condition()
    pending = 0  # calls sent and not yet answered, under `flight`
    records = []  # [pool index, due, sent, recv or None, got]

    def answered(record, future):
        nonlocal pending
        try:
            got = future.result()
        except grpc.RpcError as e:
            # a deadline that passed is no answer; any other status is one
            got = f"{UNANSWERED}: {e.code()}" \
                if e.code() == grpc.StatusCode.DEADLINE_EXCEEDED \
                else f"rpc error: {e.code()}"
        except Exception as e:  # noqa: BLE001 — a cancelled call, at the end
            got = f"{UNANSWERED}: {type(e).__name__}"
        record[3], record[4] = clock.now_ns(), got
        with flight:
            pending -= 1
            flight.notify()

    try:
        grpc.channel_ready_future(channel).result(timeout=30)
        n = len(pool)
        for k, due in enumerate(due_ns.tolist()):
            while True:  # sleep to just short of it, then yield until it
                wait_ns = due - clock.now_ns()
                if wait_ns <= 0:
                    break
                time.sleep(max(wait_ns - 200_000, 0) / 1e9)
            with flight:  # counted for the drain below, and for nothing else
                pending += 1
            record = [k % n, due, clock.now_ns(), None, UNANSWERED]
            records.append(record)
            future = call.future(pool[k % n].body, timeout=timeout_s)
            future.add_done_callback(functools.partial(answered, record))
        with flight:  # every call has its own deadline, so this ends
            flight.wait_for(lambda: pending == 0, timeout=timeout_s + 10)
    finally:
        channel.close()
        gave_up = clock.now_ns()
        for idx, due, sent, recv, got in records:
            recv = gave_up if recv is None else recv
            out.append((idx, sent, recv, recv - due, got, due))


def main(conn, spec: dict) -> None:
    """Process entry (multiprocessing spawn). Protocol on `conn`:
    -> ("ready", seconds spent building pools)
    <- (warm_start, window_start, window_end) as wall-clock seconds
    -> ("done", results dict)  |  ("error", traceback)"""
    try:
        t0 = time.time()
        traffic = Traffic(spec["mix"], spec["key_params"], spec["residents"],
                          spec["seed"])
        pools = {c: traffic.build_pool(c) for c in spec["clients"]}
        conn.send(("ready", time.time() - t0))
        warm_start, win_start, win_end = conn.recv()
        logs = {c: [] for c in pools}
        errors = []
        mix = spec["mix"]
        if mix["loop"] == "open":
            clock = _Clock()
            threads = [threading.Thread(
                target=_sender, daemon=True,
                args=(spec["address"], pools[c],
                      _due_ns(traffic, c, warm_start, win_end),
                      mix["call_timeout_s"], clock, logs[c], errors))
                for c in pools]
        else:
            threads = [threading.Thread(
                target=_caller, daemon=True,
                args=(spec["address"], pools[c], mix["call_timeout_s"],
                      warm_start, win_end, logs[c], errors)) for c in pools]
        for t in threads:
            t.start()
        time.sleep(max(win_start - time.time(), 0))
        cpu0 = time.process_time()
        time.sleep(max(win_end - time.time(), 0))
        cpu_s = time.process_time() - cpu0
        for t in threads:
            t.join(timeout=mix["call_timeout_s"] + 30)
            if t.is_alive():
                raise RuntimeError("a caller did not finish")
        if errors:
            raise RuntimeError("a caller failed:\n" + errors[0])
        conn.send(("done", _reduce(pools, logs, win_start, win_end, cpu_s)))
    except Exception:  # noqa: BLE001 — reported to the parent, which fails the run
        conn.send(("error", traceback.format_exc()))
    finally:
        conn.close()


def _reduce(pools, logs, win_start, win_end, cpu_s) -> dict:
    """A closed loop's call belongs to the window when it was sent and
    answered inside it. An open loop's (its log entries end with the due
    instant) belongs by that instant, wherever its answer lands: a slow
    call is not censored at the window's end, and one never answered fails
    its decisions."""
    ws, we = int(win_start * 1e9), int(win_end * 1e9)
    lat, audits, notes = [], [], []
    due_ns, lag_ns = [], []
    calls = decisions = attempted = failed = malformed = all_calls = 0
    unanswered = 0
    for c, log in logs.items():
        pool = pools[c]
        for seq, (idx, w0, w1, lat_ns, got, *due) in enumerate(log):
            call = pool[idx]
            n = len(call.limits)
            if due:
                in_window = ws <= due[0] < we
                if in_window:
                    due_ns.append(due[0])
                    lag_ns.append(w0 - due[0])
                    unanswered += isinstance(got, str) and \
                        got.startswith(UNANSWERED)
            else:
                in_window = w0 >= ws and w1 <= we
            all_calls += 1
            bad = 0
            if isinstance(got, str):
                bad = n
                notes.append(f"client {c} call {seq}: {got}")
            else:
                try:
                    rows = decode_responses(got)
                except (ValueError, IndexError) as e:
                    rows = []
                    notes.append(f"client {c} call {seq}: {e}")
                if len(rows) != n:
                    bad = n
                    notes.append(f"client {c} call {seq}: {len(rows)} "
                                 f"answers to {n} requests")
                else:
                    a = np.asarray([r[:4] for r in rows], np.int64)
                    wrong = ((a[:, 0] < 0) | (a[:, 0] > 1)
                             | (a[:, 1] != call.limits) | (a[:, 2] < 0)
                             | (a[:, 2] > a[:, 1])
                             | np.asarray([bool(r[4]) for r in rows]))
                    bad = int(wrong.sum())
                    if bad:
                        j = int(np.nonzero(wrong)[0][0])
                        notes.append(f"client {c} call {seq} #{j}: "
                                     f"{rows[j]} limit {call.limits[j]}")
                    if len(call.audit_pos):
                        p = call.audit_pos
                        rec = np.empty((len(p), 9), np.int64)
                        rec[:, 0] = call.audit_ids.astype(np.int64)
                        rec[:, 1], rec[:, 2] = w0, w1
                        rec[:, 3:7] = a[p]
                        rec[:, 7] = p
                        rec[:, 8] = (c << 32) | seq
                        audits.append(rec)
            malformed += bad
            if in_window:
                calls += 1
                attempted += n
                failed += bad
                decisions += n - bad
                lat.append(lat_ns)
    return {
        "lat_ns": np.asarray(lat, np.int64),
        "calls": calls, "decisions": decisions, "attempted": attempted,
        "failed": failed, "malformed_all": malformed, "all_calls": all_calls,
        "cpu_s": cpu_s, "notes": notes[:5],
        # open loops: when each of the window's calls was due (`lat_ns` is
        # counted from there), how late it was sent, how many got no answer
        "due_ns": np.asarray(due_ns, np.int64),
        "lag_ns": np.asarray(lag_ns, np.int64), "unanswered": int(unanswered),
        # columns: check.COLS
        "audits": np.concatenate(audits) if audits
        else np.empty((0, 9), np.int64),
    }

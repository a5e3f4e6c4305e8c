"""One load-generator process: a few closed-loop callers, each with its own
connection and its own seeded pool of pre-serialised calls.

Imports nothing of the program and no JAX. Inside the run it only sends,
receives and appends; responses are decoded and checked for shape after
the window, and the answers for audited keys go back to the parent with
the send and receive instants of their calls.
"""

from __future__ import annotations

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
        threads = [threading.Thread(
            target=_caller, daemon=True,
            args=(spec["address"], pools[c], spec["mix"]["call_timeout_s"],
                  warm_start, win_end, logs[c], errors)) for c in pools]
        for t in threads:
            t.start()
        time.sleep(max(win_start - time.time(), 0))
        cpu0 = time.process_time()
        time.sleep(max(win_end - time.time(), 0))
        cpu_s = time.process_time() - cpu0
        for t in threads:
            t.join(timeout=spec["mix"]["call_timeout_s"] + 30)
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
    ws, we = int(win_start * 1e9), int(win_end * 1e9)
    lat, audits, notes = [], [], []
    calls = decisions = attempted = failed = malformed = all_calls = 0
    for c, log in logs.items():
        pool = pools[c]
        for seq, (idx, w0, w1, lat_ns, got) in enumerate(log):
            call = pool[idx]
            n = len(call.limits)
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
        # columns: check.COLS
        "audits": np.concatenate(audits) if audits
        else np.empty((0, 9), np.int64),
    }

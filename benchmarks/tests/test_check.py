"""The comparison that decides `correct` must be able to fail."""

import json
import os

import numpy as np
import pytest

import check
import loadgen
import oracle
import wire
from conftest import BENCH
from traffic import Call, Traffic

STAMP = 1_700_000_000_000
RESIDENTS = 4096


@pytest.fixture(scope="module")
def traffic():
    with open(os.path.join(BENCH, "configs", "node-1chip-10m.json")) as f:
        conf = json.load(f)
    with open(os.path.join(BENCH, "workloads", "node10m.batch1000.json")) as f:
        mix = json.load(f)
    return Traffic(mix, conf["key_model"], RESIDENTS, seed=7)


def serve(traffic, raced: bool):
    """Audit rows of a sound server: every audited id asked 12 times, the
    answers computed by the oracle at a pinned clock. With `raced` the
    calls overlap in time, so the checker may not assume an order."""
    ids = np.arange(0, 600, dtype=np.uint64)
    f = traffic.model.fields(ids)
    rows0 = traffic.model.resident_rows(ids, STAMP)
    out = []
    for k, kid in enumerate(ids.tolist()):
        table = {0: oracle.Row(*[int(v) for v in rows0[k]])}
        for i in range(12):
            now = STAMP + 60_000 + i * 50
            a = oracle.decide(
                table, 0, hits=int(f["hits"][k]), limit=int(f["limit"][k]),
                duration=traffic.model.duration_ms,
                algorithm=int(f["algorithm"][k]), behavior=0, now=now)
            send = now * 1_000_000 - (400_000_000 if raced else 2_000_000)
            out.append([kid, send, now * 1_000_000 + 2_000_000, a.status,
                        a.limit, a.remaining, a.reset_time, 0, i])
    return np.asarray(out, np.int64), f


def token_key(f, limit):
    """An id whose bucket is a TOKEN_BUCKET of the given limit."""
    return int(np.nonzero((f["algorithm"] == 0) & (f["limit"] == limit))[0][0])


@pytest.mark.parametrize("raced", [False, True])
def test_sound_answers_pass(traffic, raced):
    audits, _ = serve(traffic, raced)
    got = check.check_audits(audits, traffic, STAMP)
    assert got["mismatches"] == 0, got["examples"]
    assert got["raced_keys" if raced else "sequential_keys"] == 600


@pytest.mark.parametrize("raced", [False, True])
def test_one_remaining_off_by_one_fails(traffic, raced):
    audits, f = serve(traffic, raced)
    kid = token_key(f, 100_000)
    row = np.nonzero(audits[:, 0] == kid)[0][5]
    audits[row, 5] += 1
    got = check.check_audits(audits, traffic, STAMP)
    assert got["mismatches"] >= 1
    assert f"{kid:#x}" in got["examples"][0]


@pytest.mark.parametrize("raced", [False, True])
def test_one_key_admitted_once_over_its_limit_fails(traffic, raced):
    """Twelve requests against a bucket of 10 that had spent part of its
    limit: the last ones are OVER_LIMIT. One of them admitted instead."""
    audits, f = serve(traffic, raced)
    kid = token_key(f, 10)
    rows = np.nonzero(audits[:, 0] == kid)[0]
    over = [r for r in rows if audits[r, 3] == oracle.OVER_LIMIT]
    assert over, "the drive must exhaust the bucket"
    audits[over[-1], 3] = oracle.UNDER_LIMIT
    got = check.check_audits(audits, traffic, STAMP)
    assert got["mismatches"] >= 1


def test_leaky_key_admitted_over_its_supply_fails(traffic):
    audits, f = serve(traffic, raced=True)
    kid = int(np.nonzero((f["algorithm"] == 1) & (f["limit"] == 10))[0][0])
    rows = np.nonzero(audits[:, 0] == kid)[0]
    audits[rows, 3] = oracle.UNDER_LIMIT  # every one of the 12 admitted
    got = check.check_audits(audits, traffic, STAMP)
    assert got["mismatches"] >= 1


def answers(n, limit=100, skip=None):
    body = b""
    for i in range(n):
        if i == skip:
            continue
        entry = b"\x10" + wire._varint(limit) + b"\x18" + wire._varint(limit - 1)
        body += b"\x0a" + wire._varint(len(entry)) + entry
    return body


@pytest.mark.parametrize("skip,malformed", [(None, 0), (3, 8)])
def test_a_missing_answer_fails_the_whole_call(skip, malformed):
    call = Call(body=b"", limits=np.full(8, 100, np.int64),
                audit_pos=np.asarray([1], np.int32),
                audit_ids=np.asarray([5], np.uint64))
    log = [(0, 1_000, 2_000, 1_000, answers(8, skip=skip))]
    got = loadgen._reduce({0: [call]}, {0: log}, 0.0, 1.0, cpu_s=0.0)
    assert got["malformed_all"] == malformed
    assert got["failed"] == malformed and got["attempted"] == 8
    assert len(got["audits"]) == (0 if malformed else 1)


def test_wire_round_trip():
    req = wire.encode_request(b"rl", b"acct:0000002a", 3, 100_000,
                              3_600_000, 1, 0)
    assert req[0] == 0x0A and b"acct:0000002a" in req
    rows = wire.decode_responses(answers(3))
    assert rows == [(0, 100, 99, 0, "")] * 3

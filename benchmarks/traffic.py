"""The one traffic generator. A traffic mix is a JSON file of parameters
under `workloads/`; this module turns (mix, key model, seed, client index)
into that client's pool of pre-serialised calls.

Parameters of a mix's `key_model`:
  zipf_exponent     popularity of the resident keys by rank (0 = uniform)
  distinct_in_call  no key twice inside one call
  hot_set           {"ranks": K, "share": s}: s of the requests go uniformly
                    to the K hottest ranks, the rest follow the Zipf law
  new_key_share     share of requests to keys the daemon has never seen
and of the mix itself: `behaviors` ([{behavior, share}], fixed per key),
`requests_per_call`, `pool_calls_per_client`; where `loop` is `open` also
`rate_per_s` (decisions a second, all clients together, as Poisson arrivals).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List

import numpy as np

from keymodel import NAME, NEW_BASE, KeyModel, mix
from wire import encode_request

SUPPORTED_BEHAVIORS = (0, 8)  # BATCHING, RESET_REMAINING: what oracle.py replays


@dataclass
class Call:
    body: bytes  # serialised GetRateLimitsReq
    limits: np.ndarray  # int64[n]: the limit each answer must echo
    audit_pos: np.ndarray  # positions of audited keys in the call
    audit_ids: np.ndarray  # their key ids


class Traffic:
    def __init__(self, mix_params: dict, key_params: dict, residents: int,
                 seed: int):
        self.p = mix_params
        self.km = mix_params["key_model"]
        self.residents = int(residents)
        self.seed = int(seed)
        self.model = KeyModel(key_params, seed)
        self.audit_one_in = int(mix_params["audit"]["sample_one_in"])
        self.audit_ranks = int(mix_params["audit"]["hottest_ranks"])
        self.behaviors = mix_params.get("behaviors") or [
            {"behavior": 0, "share": 1.0}]
        for b in self.behaviors:
            if int(b["behavior"]) not in SUPPORTED_BEHAVIORS:
                raise ValueError(
                    f"behavior {b['behavior']} is not replayed by "
                    "benchmarks/oracle.py")
        # rank -> key id: an affine permutation of the residents
        a = int(mix(np.asarray([0]), seed, 4)[0] % np.uint64(residents)) | 1
        while math.gcd(a, self.residents) != 1:
            a += 2
        self._a = a
        self._b = int(mix(np.asarray([1]), seed, 4)[0] % np.uint64(residents))
        self._cdf = None

    # ---- keys

    def rank_to_id(self, ranks) -> np.ndarray:
        r = np.asarray(ranks, np.uint64)
        return (r * np.uint64(self._a) + np.uint64(self._b)) \
            % np.uint64(self.residents)

    def audited(self, ids) -> np.ndarray:
        """The seeded 1-in-N sample (by id; the hottest ranks are added by
        the caller, who knows the ranks)."""
        return mix(ids, self.seed, 3) % np.uint64(self.audit_one_in) == 0

    def behavior_of(self, ids) -> np.ndarray:
        u = (mix(ids, self.seed, 5) >> np.uint64(11)).astype(np.float64) \
            / float(1 << 53)
        edges = np.cumsum([float(b["share"]) for b in self.behaviors])
        idx = np.minimum(np.searchsorted(edges, u, side="right"),
                         len(self.behaviors) - 1)
        return np.asarray([int(b["behavior"]) for b in self.behaviors],
                          np.int64)[idx]

    def _draw_ranks(self, rng, n: int) -> np.ndarray:
        if self._cdf is None:
            w = np.arange(1, self.residents + 1, dtype=np.float64) \
                ** -float(self.km["zipf_exponent"])
            self._cdf = np.cumsum(w)
            self._cdf /= self._cdf[-1]
        ranks = np.searchsorted(self._cdf, rng.random(n), side="right")
        ranks = np.minimum(ranks, self.residents - 1)
        hot = self.km.get("hot_set") or {}
        if hot.get("share", 0) > 0:
            pick = rng.random(n) < float(hot["share"])
            ranks[pick] = rng.integers(0, int(hot["ranks"]), int(pick.sum()))
        return ranks

    # ---- one client's schedule (open loops)

    def arrival_offsets_ns(self, client: int, n: int) -> np.ndarray:
        """The instants, in ns after the client's start, at which its first
        `n` calls are due: exponential gaps whose mean gives each of the
        mix's clients a 1/clients share of `rate_per_s`, so the clients
        together are one Poisson stream at the mix's rate. A pure function
        of (mix, seed, client)."""
        mean_gap_s = int(self.p["clients"]) * int(
            self.p["requests_per_call"]) / float(self.p["rate_per_s"])
        rng = np.random.default_rng([self.seed, client, 0x64756573])
        return np.cumsum(rng.exponential(mean_gap_s * 1e9, n)).astype(np.int64)

    # ---- one client's pool

    def build_pool(self, client: int) -> List[Call]:
        rng = np.random.default_rng([self.seed, client, 0x706F6F6C])
        n_req = int(self.p["requests_per_call"])
        n_calls = int(self.p["pool_calls_per_client"])
        distinct = bool(self.km["distinct_in_call"])
        new_share = float(self.km["new_key_share"])
        next_new = NEW_BASE * (client + 1)
        picks = []  # per call: (ranks or -1 for a new key, ids)
        for _ in range(n_calls):
            ranks = self._draw_ranks(rng, n_req + (n_req // 3 if distinct else 0))
            if distinct:
                while True:
                    _, first = np.unique(ranks, return_index=True)
                    if len(first) >= n_req:
                        break
                    ranks = np.concatenate(
                        [ranks, self._draw_ranks(rng, n_req)])
                ranks = ranks[np.sort(first)[:n_req]]
            ids = self.rank_to_id(ranks)
            fresh = rng.random(n_req) < new_share
            k = int(fresh.sum())
            if k:
                ids[fresh] = np.arange(next_new, next_new + k, dtype=np.uint64)
                ranks = np.where(fresh, np.iinfo(np.int64).max, ranks)
                next_new += k
            picks.append((ranks, ids))
        all_ids = np.unique(np.concatenate([ids for _, ids in picks]))
        f = self.model.fields(all_ids)
        beh = self.behavior_of(all_ids)
        keys = self.model.unique_keys(all_ids)
        name = NAME.encode()
        dur = self.model.duration_ms
        enc = {}
        limit_of = {}
        for j, kid in enumerate(all_ids.tolist()):
            enc[kid] = encode_request(
                name, keys[j].tobytes(), int(f["hits"][j]),
                int(f["limit"][j]), dur, int(f["algorithm"][j]), int(beh[j]))
            limit_of[kid] = int(f["limit"][j])
        pool = []
        for ranks, ids in picks:
            id_list = ids.tolist()
            aud = self.audited(ids) | (ranks < self.audit_ranks)
            pos = np.nonzero(aud)[0].astype(np.int32)
            pool.append(Call(
                body=b"".join([enc[k] for k in id_list]),
                limits=np.fromiter((limit_of[k] for k in id_list), np.int64,
                                   len(id_list)),
                audit_pos=pos, audit_ids=ids[pos].copy()))
        return pool


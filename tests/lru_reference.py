"""The plain reference of a table that is smaller than its key space: an
`OrderedDict` of at most `capacity` keys, least recently used first, over
`gubernator_tpu/ops/oracle.py`'s per-key arithmetic. One request at a time,
pure Python; nothing of the engine, its directory or its staging is used.

What it states (the guarantees of a node whose key space outgrows its
table; upstream architecture.md:5-11: losing the oldest bucket is accepted):

- a key the table does not hold answers as a new bucket (`UNDER_LIMIT`,
  `remaining = limit - hits`), whatever it had spent before it was evicted;
- a miss on a full table evicts the least recently used key, and never a
  key this window has already served (a window is one launch: its lanes
  scatter to distinct rows, so a key decided in it keeps its row until the
  window is through). A key that stands later in the window is not served
  yet and can go: it then comes back as a new bucket in its turn, as it
  would had the requests come one by one;
- the table never holds more than `capacity` keys.

A call wider than a window is applied a window at a time (`window` lanes,
the engine's `max_width`), in the call's order. Calls here keep their keys
distinct: a key twice in one call is another path's business
(tests/test_hot_deployment.py).
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List

from gubernator_tpu.ops.oracle import Row, oracle_answer


class LruReference:
    def __init__(self, capacity: int, window: int):
        self.capacity = int(capacity)
        self.window = int(window)
        self.lru: "OrderedDict[str, None]" = OrderedDict()  # oldest first
        self.rows: Dict[str, Row] = {}
        self.evictions = 0
        self.fresh = 0  # requests answered from a row made for them
        self.evicted: List[str] = []  # in the order they went

    def __len__(self) -> int:
        return len(self.lru)

    def __contains__(self, key: str) -> bool:
        return key in self.lru

    def seed(self, key: str, row: Row) -> None:
        """A resident restored before any traffic (no eviction is needed:
        the caller seeds at most `capacity` keys)."""
        assert key not in self.lru and len(self.lru) < self.capacity
        self.lru[key] = None
        self.rows[key] = row

    def apply(self, requests, now: int) -> list:
        """One call at `now`: the answers, in the call's order."""
        out = []
        for lo in range(0, len(requests), self.window):
            served = set()
            for req in requests[lo:lo + self.window]:
                key = req.hash_key()
                if key in self.lru:
                    self.lru.move_to_end(key)
                else:
                    if len(self.lru) >= self.capacity:
                        self._evict(served)
                    self.lru[key] = None
                    self.fresh += 1
                served.add(key)
                out.append(oracle_answer(self.rows, req, now))
        return out

    def _evict(self, served) -> None:
        victim = next(k for k in self.lru if k not in served)
        del self.lru[victim]
        self.rows.pop(victim, None)
        self.evictions += 1
        self.evicted.append(victim)

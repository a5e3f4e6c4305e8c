"""Host key directory: string key -> device table slot.

The reference stores buckets in a per-key LRU of Go structs
(reference: cache.go:53-165). Here the bucket state is dense device memory,
and the only per-key host structure is this directory mapping keys to row
indices, with LRU recycling when the table is full. Losing a slot loses that
key's state — the same accepted tradeoff as the reference's LRU eviction and
restart behavior (reference: architecture.md:5-11).

The pure-Python implementation below is the fallback; the C++ directory
(native/keydir.cpp, loaded via gubernator_tpu.native) is the production path
at millions of lookups/sec.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Sequence, Tuple

import numpy as np

from gubernator_tpu.native import pack_keys, unpack_keys


class KeyDirectory:
    """LRU map key -> slot over a fixed slot capacity."""

    def __init__(self, capacity: int):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = capacity
        self._map: "OrderedDict[str, int]" = OrderedDict()
        self._free: List[int] = list(range(capacity - 1, -1, -1))
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._map)

    def __contains__(self, key: str) -> bool:
        return key in self._map

    def lookup_inject(self, keys: Sequence[str]):
        """Native-API twin: (slots, fresh, inject). The python directory
        has no row mirrors (the native lone-request fast path lives in
        keydir.cpp), so the inject list is always empty."""
        slots, fresh = self.lookup(keys)
        import numpy as np

        return slots, fresh, np.empty((0, 8), np.int64)

    def lookup(self, keys: Sequence[str]) -> Tuple[List[int], List[bool]]:
        """Map keys to slots, assigning (and recycling LRU) as needed.

        Returns (slots, fresh) where fresh[i] means the slot was newly
        assigned to keys[i] and its device row must be treated as vacant.
        Duplicate keys in one call share a slot; only the first sees fresh.

        Keys of the current call are pinned: eviction never recycles a slot
        handed out earlier in the same call, so one kernel round never
        scatters two lanes to one row. Callers must keep
        len(set(keys)) <= capacity (the engine chunks accordingly).
        """
        slots: List[int] = []
        fresh: List[bool] = []
        pinned = set()
        for key in keys:
            slot = self._map.get(key)
            if slot is not None:
                self._map.move_to_end(key)
                pinned.add(key)
                slots.append(slot)
                fresh.append(False)
                continue
            if self._free:
                slot = self._free.pop()
            else:
                slot = None
                for victim in self._map:  # LRU order; skip this call's keys
                    if victim not in pinned:
                        slot = self._map.pop(victim)
                        self.evictions += 1
                        break
                if slot is None:
                    raise RuntimeError(
                        f"key directory over-committed: >{self.capacity} "
                        "distinct keys in one lookup")
            self._map[key] = slot
            pinned.add(key)
            slots.append(slot)
            fresh.append(True)
        return slots, fresh

    def drop(self, key: str) -> None:
        """Forget a key, returning its slot to the free list."""
        slot = self._map.pop(key, None)
        if slot is not None:
            self._free.append(slot)

    def keys(self) -> List[str]:
        return list(self._map.keys())

    def items(self) -> List[Tuple[str, int]]:
        return list(self._map.items())

    def peek_slot(self, key: str) -> int:
        """Slot for key without recency effects; -1 if absent."""
        return self._map.get(key, -1)

    def slots_live(self, slots) -> np.ndarray:
        """Native-API twin: bool[n], which of `slots` hold a key."""
        held = np.fromiter(self._map.values(), np.int64, count=len(self._map))
        return np.isin(np.asarray(slots, np.int64), held)


def resolve_slots(directory, slots) -> Dict[int, str]:
    """slot -> key for the slots that hold a key in `directory` right now;
    slots without a live entry (free, recycled away, out of range) are
    simply absent from the result.

    The native directory answers by index (`keys_for_slots`): the cost is
    the slots asked, whatever the directory holds, and its mutex is held
    for at most one window's worth of slots at a time, so the tickers that
    call this (ledger audit, hot-key tracker, cartographer) never stall a
    window's prep. The directory keeps serving meanwhile, so each slot is
    answered with the key that holds it at that instant: a slot recycled
    between the decision and this call names its new key, as it did under
    the whole-directory dump this replaced. The Python twin has only the
    key -> slot map and is walked."""
    want = np.unique(np.asarray(
        slots if isinstance(slots, np.ndarray) else list(slots), np.int64))
    want = want[(want >= 0) & (want < directory.capacity)]
    if not want.size:
        return {}
    if hasattr(directory, "keys_for_slots"):
        blob, off = directory.keys_for_slots(want)
        live = np.flatnonzero(off[1:] > off[:-1])
        return dict(zip(want[live].tolist(), unpack_keys(blob, off, live)))
    asked = set(want.tolist())
    return {int(s): key for key, s in directory.items() if int(s) in asked}


def peek_slots(directory, keys) -> np.ndarray:
    """key -> slot (int64, -1 where `directory` holds no such key), with no
    effect on recency. `keys` is a sequence of str or a packed arena
    `(blob, offsets)` as `native.pack_keys` makes: a caller that asks for
    the same keys again and again (the ledger audit, for its tracked keys
    every tick) packs them once. The native directory answers an arena in
    one C pass with the GIL released; the Python twin is asked key by
    key."""
    packed = isinstance(keys, tuple)
    if hasattr(directory, "peek_slots_raw"):
        blob, offsets = keys if packed else pack_keys(keys)
        return directory.peek_slots_raw(blob, offsets).astype(np.int64)
    if packed:
        keys = unpack_keys(*keys)
    return np.fromiter(map(directory.peek_slot, keys), np.int64,
                       count=len(keys))

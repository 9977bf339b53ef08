"""Plain references for the keyed-stage pipelines: numpy only.

Nothing here imports the system under test. A stage is judged by what a
keyed stage guarantees, worked out again from the traffic alone:

* windowed state: a tuple of interval ``i`` sees its key's tuples of
  intervals ``i - window .. i`` (the state of the last ``window`` closed
  intervals, then its own interval), so WordCount's j-th tuple of key k in
  interval i emits ``W0_i[k] + j``, with ``W0_i`` the count over intervals
  ``i - window .. i - 1`` (paper Sec. II-A: the state of ``T_{i-w}`` is
  erased after ``T_i`` finishes);
* exactly once: every tuple is counted once, so these sums are exact
  integers;
* the route: ``F(k) = table[k]`` where the routing table holds k, else
  ``fmix32(k ^ seed) % tasks`` (the 32-bit murmur3 finalizer);
* the controller: each interval's per-task load is the sum of the costs of
  the tuples routed to the task; a rebalance follows exactly the intervals
  whose load imbalance ``max(L - mean) / mean`` exceeds ``theta_max``; a
  plan leaves that interval's loads within ``theta_max`` and the table
  within ``table_max``; the table of the last plan before an interval
  routes that interval.

The routing tables are what the controller answered, so they are checked
against these rules rather than taken as given: loads and ownership are
recomputed from them with this module's own hash.

The ``check_*`` functions return numbers that are 0 on a sound run: counts
of keys, cells or intervals that disagree, and absolute gaps.
"""

from __future__ import annotations

from typing import Dict, Iterable, Iterator, List, Sequence, Tuple

import numpy as np


def fmix32(keys: np.ndarray, seed: int) -> np.ndarray:
    """murmur3's 32-bit finalizer of ``key ^ seed``."""
    with np.errstate(over="ignore"):
        h = keys.astype(np.uint32) ^ np.uint32(int(seed) & 0xFFFFFFFF)
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
    return h


def hashed(domain: int, tasks: int, seed: int) -> np.ndarray:
    """``fmix32(k ^ seed) % tasks`` for every key of ``[0, domain)``."""
    return (fmix32(np.arange(domain, dtype=np.int64), seed)
            % np.uint32(tasks)).astype(np.int64)


def route(base: np.ndarray, table: Tuple[np.ndarray, np.ndarray]
          ) -> np.ndarray:
    """Dense ``F(k)``: the hashed destinations ``base``, then the table's
    overrides."""
    dest = base.copy()
    tkeys, tdests = table
    dest[tkeys] = tdests
    return dest


def imbalance(loads: np.ndarray) -> float:
    mean = float(np.mean(loads))
    if mean <= 0.0:
        return 0.0
    return max(0.0, float(np.max(loads - mean) / mean))


def window_walk(counts: Sequence[np.ndarray], window: int,
                dtype=np.int64) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """``(c_i, W0_i)`` for each interval: its per-key counts and the count
    over the ``window`` intervals before it. ``dtype`` is the arithmetic the
    window is kept in."""
    ring: List[np.ndarray] = []
    for c in counts:
        c = c.astype(dtype)
        w0 = np.zeros_like(c)
        for prev in ring:
            w0 = w0 + prev
        yield c, w0
        ring = (ring + [c])[-window:]


class KeyedCounts:
    """What a windowed count stage must answer for a stream of intervals;
    ``dtype`` is the arithmetic its window is kept in."""

    def __init__(self, domain: int, window: int, dtype=np.int64):
        self.domain = domain
        self.window = window
        self.dtype = dtype
        self.counts: List[np.ndarray] = []
        self.out_key = np.zeros(0, np.int64)
        self.out_val = np.zeros(0, np.int64)
        self.emitted = 0

    def run(self, intervals: Sequence[np.ndarray]) -> "KeyedCounts":
        self.counts = [np.bincount(k, minlength=self.domain)
                       .astype(np.int32) for k in intervals]
        ever = np.zeros(self.domain, dtype=bool)
        out = np.zeros(self.domain, dtype=np.int64)
        for c, w0 in window_walk(self.counts, self.window, self.dtype):
            seen = c > 0
            # the j-th tuple of a key emits w0 + j; a key's last emit is
            # its window total, and its emits sum to c*w0 + c(c+1)/2
            cs, ws = c[seen].astype(np.int64), w0[seen].astype(np.int64)
            self.emitted += int(np.dot(cs, ws)) + int(np.dot(cs, cs + 1) // 2)
            out[seen] = (w0 + c)[seen].astype(np.int64)
            ever |= seen
        self.out_key = np.nonzero(ever)[0]
        self.out_val = out[self.out_key]
        return self

    def held(self) -> Tuple[np.ndarray, np.ndarray]:
        """Keys with state after the last interval, and how many of the
        last ``window`` intervals each appeared in."""
        slots = np.zeros(self.domain, dtype=np.int64)
        for c in self.counts[-self.window:]:
            slots += c > 0
        keys = np.nonzero(slots)[0]
        return keys, slots[keys]


def tables_in_force(n_intervals: int, plans: Sequence[dict]
                    ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Routing table of each interval 1..n: the last plan made at the end
    of an earlier interval, else empty."""
    empty = (np.zeros(0, np.int64), np.zeros(0, np.int64))
    out, cur, by_iv = [], empty, {p["interval"]: p for p in plans}
    for i in range(1, n_intervals + 1):
        out.append(cur)
        if i in by_iv:
            cur = (by_iv[i]["keys"], by_iv[i]["dests"])
    return out


def check_controller(stage: dict, costs: Iterable[np.ndarray], *,
                     domain: int, theta_max: float, table_max: int,
                     prefix: str) -> Dict[str, float]:
    """Loads, triggers, tables and plans of one stage against its costs
    per key and interval (one array per interval the stage reported)."""
    n = len(stage["loads"])
    tasks, seed = stage["tasks"], stage["hash_seed"]
    plans = stage["plans"]
    tables = tables_in_force(n, plans)
    planned = {p["interval"] for p in plans}
    loads_wrong = triggers_wrong = tables_wrong = plans_over = 0
    base = hashed(domain, tasks, seed)
    dest, dest_of = None, None
    for i, (cost, table) in enumerate(zip(costs, tables), start=1):
        if dest_of is not table:
            dest, dest_of = route(base, table), table
        loads = np.bincount(dest, weights=cost, minlength=tasks)
        loads_wrong += int(np.sum(loads != stage["loads"][i - 1]))
        triggers_wrong += int((imbalance(loads) > theta_max)
                              != (i in planned))
        size = int(table[0].size)
        tables_wrong += int(size != stage["table_size"][i - 1]
                            or size > table_max)
        if i in planned:
            p = [q for q in plans if q["interval"] == i][0]
            after = route(base, (p["keys"], p["dests"]))
            plans_over += int(
                imbalance(np.bincount(after, weights=cost, minlength=tasks))
                > theta_max or p["keys"].size > table_max)
    return {f"{prefix}loads_wrong": loads_wrong,
            f"{prefix}triggers_wrong": triggers_wrong,
            f"{prefix}tables_wrong": tables_wrong,
            f"{prefix}plans_over": plans_over}


def check_owners(stage: dict, held_keys: np.ndarray, slots: np.ndarray, *,
                 domain: int, slot_bytes: float,
                 prefix: str) -> Dict[str, float]:
    """Each key with state lives on the task the final table routes it to,
    no other key has state, and its state holds one slot per interval it
    appeared in."""
    final = ((stage["plans"][-1]["keys"], stage["plans"][-1]["dests"])
             if stage["plans"] else (np.zeros(0, np.int64),) * 2)
    dest = route(hashed(domain, stage["tasks"], stage["hash_seed"]), final)
    want_owner = np.full(domain, -1, dtype=np.int64)
    want_owner[held_keys] = dest[held_keys]
    want_size = np.zeros(domain)
    want_size[held_keys] = slot_bytes * slots
    got_owner = np.full(domain, -1, dtype=np.int64)
    got_size = np.zeros(domain)
    twice = 0
    for task, (keys, sizes) in enumerate(stage["owned"]):
        twice += int(np.sum(got_owner[keys] >= 0))
        got_owner[keys] = task
        got_size[keys] = sizes
    return {f"{prefix}owners_wrong": int(np.sum(got_owner != want_owner))
            + twice,
            f"{prefix}sizes_wrong": int(np.sum(got_size != want_size))}


def check_outputs(stage: dict, ref: KeyedCounts,
                  prefix: str) -> Dict[str, float]:
    """Per-key outputs (the last emit of each key) and the sum of emits."""
    got_k, got_v = stage["out_keys"], stage["out_vals"]
    order = np.argsort(got_k, kind="stable")
    got_k, got_v = got_k[order], got_v[order]
    if got_k.size == ref.out_key.size and np.array_equal(got_k, ref.out_key):
        wrong = int(np.sum(got_v != ref.out_val))
    else:
        common, gi, ri = np.intersect1d(got_k, ref.out_key,
                                        return_indices=True)
        wrong = (int(np.sum(got_v[gi] != ref.out_val[ri]))
                 + (got_k.size - common.size) + (ref.out_key.size
                                                 - common.size))
    return {f"{prefix}outputs_wrong": wrong,
            f"{prefix}emitted_gap": abs(float(stage["emitted_sum"])
                                        - float(ref.emitted))}

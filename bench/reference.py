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

Everything is worked out over the key ids an interval holds, never over a
dense ``[0, domain)``: an interval's counts are ``(ids, counts)``, and
memory follows the keys present, not the largest id.

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


Table = Tuple[np.ndarray, np.ndarray]
NO_KEYS = np.zeros(0, np.int64)
NO_TABLE: Table = (NO_KEYS, NO_KEYS)


def find(ids: np.ndarray, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Where each of ``keys`` sits in the sorted, distinct ``ids``, and
    whether it is there."""
    pos = np.searchsorted(ids, keys)
    there = pos < ids.size
    there[there] = ids[pos[there]] == keys[there]
    return pos, there


def values_at(ids: np.ndarray, vals: np.ndarray, keys: np.ndarray
              ) -> np.ndarray:
    """``vals`` of the sorted, distinct ``ids`` at each of ``keys``, 0
    where ``ids`` lacks it."""
    out = np.zeros(keys.size, vals.dtype)
    pos, there = find(ids, keys)
    out[there] = vals[pos[there]]
    return out


def route(ids: np.ndarray, tasks: int, seed: int, table: Table
          ) -> np.ndarray:
    """``F(k)`` of each of the sorted, distinct key ids ``ids``: the
    table's destination where it holds k, else ``fmix32(k ^ seed) %
    tasks``."""
    dest = (fmix32(ids, seed) % np.uint32(tasks)).astype(np.int64)
    tkeys, tdests = table
    pos, there = find(ids, tkeys)
    dest[pos[there]] = tdests[there]
    return dest


def hashed(domain: int, tasks: int, seed: int) -> np.ndarray:
    """``fmix32(k ^ seed) % tasks`` for every key of ``[0, domain)``."""
    return route(np.arange(domain, dtype=np.int64), tasks, seed, NO_TABLE)


def present(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """The distinct ids of ``keys``, ascending, and how often each occurs
    (int32): a count over the band of ids present where it is narrow,
    else a sort."""
    keys = np.asarray(keys, np.int64)
    if keys.size == 0:
        return NO_KEYS, np.zeros(0, np.int32)
    low = int(keys.min())
    if int(keys.max()) - low < 4 * keys.size:
        counts = np.bincount(keys - low)
        ids = np.flatnonzero(counts)
        return ids + low, counts[ids].astype(np.int32)
    ids, counts = np.unique(keys, return_counts=True)
    return ids, counts.astype(np.int32)


def imbalance(loads: np.ndarray) -> float:
    mean = float(np.mean(loads))
    if mean <= 0.0:
        return 0.0
    return max(0.0, float(np.max(loads - mean) / mean))


def window_walk(counts: Sequence[Tuple[np.ndarray, np.ndarray]],
                window: int, dtype=np.int64
                ) -> Iterator[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """``(ids_i, c_i, W0_i)`` for each interval: the key ids it holds, their
    counts, and each one's count over the ``window`` intervals before it.
    ``dtype`` is the arithmetic the window is kept in."""
    ring: List[Tuple[np.ndarray, np.ndarray]] = []
    for ids, c in counts:
        c = c.astype(dtype)
        w0 = np.zeros_like(c)
        for prev_ids, prev in ring:
            w0 = w0 + values_at(prev_ids, prev, ids)
        yield ids, c, w0
        ring = (ring + [(ids, c)])[-window:]


def last_of(keys: Sequence[np.ndarray], vals: Sequence[np.ndarray]
            ) -> Tuple[np.ndarray, np.ndarray]:
    """The distinct keys of the concatenated ``keys``, ascending, each with
    the value of its last entry."""
    k, v = np.concatenate(keys)[::-1], np.concatenate(vals)[::-1]
    out_key, first = np.unique(k, return_index=True)
    return out_key, v[first]


class KeyedCounts:
    """What a windowed count stage must answer for a stream of intervals;
    ``dtype`` is the arithmetic its window is kept in."""

    def __init__(self, window: int, dtype=np.int64):
        self.window = window
        self.dtype = dtype
        self.counts: List[Tuple[np.ndarray, np.ndarray]] = []
        self.out_key = np.zeros(0, np.int64)
        self.out_val = np.zeros(0, np.int64)
        self.emitted = 0

    def run(self, intervals: Iterable[np.ndarray]) -> "KeyedCounts":
        self.counts = [present(k) for k in intervals]
        keys, vals = [NO_KEYS], [NO_KEYS]
        for ids, c, w0 in window_walk(self.counts, self.window, self.dtype):
            seen = c > 0
            # the j-th tuple of a key emits w0 + j; a key's last emit is
            # its window total, and its emits sum to c*w0 + c(c+1)/2
            cs, ws = c[seen].astype(np.int64), w0[seen].astype(np.int64)
            self.emitted += int(np.dot(cs, ws)) + int(np.dot(cs, cs + 1) // 2)
            keys.append(ids[seen])
            vals.append((w0 + c)[seen].astype(np.int64))
        self.out_key, self.out_val = last_of(keys, vals)
        return self

    def held(self) -> Tuple[np.ndarray, np.ndarray]:
        """Keys with state after the last interval, and how many of the
        last ``window`` intervals each appeared in."""
        recent = [ids for ids, _ in self.counts[-self.window:]]
        keys, slots = np.unique(np.concatenate([NO_KEYS] + recent),
                                return_counts=True)
        return keys, slots.astype(np.int64)


def tables_in_force(n_intervals: int, plans: Sequence[dict]) -> List[Table]:
    """Routing table of each interval 1..n: the last plan made at the end
    of an earlier interval, else empty."""
    out, cur, by_iv = [], NO_TABLE, {p["interval"]: p for p in plans}
    for i in range(1, n_intervals + 1):
        out.append(cur)
        if i in by_iv:
            cur = (by_iv[i]["keys"], by_iv[i]["dests"])
    return out


def check_controller(stage: dict,
                     costs: Iterable[Tuple[np.ndarray, np.ndarray]], *,
                     theta_max: float, table_max: int,
                     prefix: str) -> Dict[str, float]:
    """Loads, triggers, tables and plans of one stage against its costs
    per key and interval: one ``(ids, costs)`` pair per interval the stage
    reported, ``ids`` sorted and distinct, a key absent costing nothing."""
    n = len(stage["loads"])
    tasks, seed = stage["tasks"], stage["hash_seed"]
    plans = stage["plans"]
    tables = tables_in_force(n, plans)
    planned = {p["interval"] for p in plans}
    loads_wrong = triggers_wrong = tables_wrong = plans_over = 0
    for i, ((ids, cost), table) in enumerate(zip(costs, tables), start=1):
        loads = np.bincount(route(ids, tasks, seed, table), weights=cost,
                            minlength=tasks)
        loads_wrong += int(np.sum(loads != stage["loads"][i - 1]))
        triggers_wrong += int((imbalance(loads) > theta_max)
                              != (i in planned))
        size = int(table[0].size)
        tables_wrong += int(size != stage["table_size"][i - 1]
                            or size > table_max)
        if i in planned:
            p = [q for q in plans if q["interval"] == i][0]
            after = route(ids, tasks, seed, (p["keys"], p["dests"]))
            plans_over += int(
                imbalance(np.bincount(after, weights=cost, minlength=tasks))
                > theta_max or p["keys"].size > table_max)
    return {f"{prefix}loads_wrong": loads_wrong,
            f"{prefix}triggers_wrong": triggers_wrong,
            f"{prefix}tables_wrong": tables_wrong,
            f"{prefix}plans_over": plans_over}


def check_owners(stage: dict, held_keys: np.ndarray, slots: np.ndarray, *,
                 slot_bytes: float, prefix: str) -> Dict[str, float]:
    """Each key with state lives on the task the final table routes it to,
    no other key has state, and its state holds one slot per interval it
    appeared in. ``held_keys`` are sorted and distinct; owners and sizes
    are compared over the keys held or owned."""
    final = ((stage["plans"][-1]["keys"], stage["plans"][-1]["dests"])
             if stage["plans"] else NO_TABLE)
    owned = stage["owned"]
    keys = np.concatenate([NO_KEYS] + [k for k, _ in owned])
    sizes = np.concatenate([np.zeros(0)] + [np.asarray(s, np.float64)
                                            for _, s in owned])
    task = np.repeat(np.arange(len(owned)), [k.size for k, _ in owned])
    # group each key's entries, in the order the tasks listed them
    order = np.argsort(keys, kind="stable")
    keys, sizes, task = keys[order], sizes[order], task[order]
    first = np.r_[True, keys[1:] != keys[:-1]][:keys.size]
    last = np.r_[first[1:], True][:keys.size]
    # an entry of a task after the first task that lists its key; the last
    # entry of a key is the one that stands
    twice = int(np.sum(task > task[first][np.cumsum(first) - 1]))
    owned_keys = keys[last]

    union = np.union1d(held_keys, owned_keys)
    want_owner = np.full(union.size, -1, dtype=np.int64)
    want_size = np.zeros(union.size)
    at = np.searchsorted(union, held_keys)
    want_owner[at] = route(held_keys, stage["tasks"], stage["hash_seed"],
                           final)
    want_size[at] = slot_bytes * slots
    got_owner = np.full(union.size, -1, dtype=np.int64)
    got_size = np.zeros(union.size)
    at = np.searchsorted(union, owned_keys)
    got_owner[at] = task[last]
    got_size[at] = sizes[last]
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

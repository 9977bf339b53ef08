"""The plain reference on hand-worked cases, and against a dense oracle."""

import tracemalloc
import types

import numpy as np
import pytest

import refcost
import reference
from repro.core.balancer.hashing import Hash32

BIG = 2**30


def test_route_is_the_hash_with_table_overrides():
    keys = np.arange(5000, dtype=np.int64)
    table = (np.array([3, 17]), np.array([14, 0]))
    dest = reference.route(keys, 15, 7, table)
    want = Hash32(15, seed=7)(keys)
    want[[3, 17]] = [14, 0]
    assert np.array_equal(dest, want)


def test_windowed_counts_by_hand():
    # window 2: a tuple of interval i counts its key over intervals i-2..i
    intervals = [np.array([1, 1, 2]), np.array([1]), np.array([2, 1]),
                 np.array([1])]
    ref = reference.KeyedCounts(2).run(intervals)
    # key 1 emits 1,2 | 3 | 4 | 3 (interval 1 left the window); key 2: 1 | 2
    assert ref.emitted == (1 + 2) + 3 + (2 + 4) + 3 + 1
    assert ref.out_key.tolist() == [1, 2]
    assert ref.out_val.tolist() == [3, 2]
    keys, slots = ref.held()
    assert keys.tolist() == [1, 2] and slots.tolist() == [2, 1]


def test_narrow_window_arithmetic_wraps():
    hot = [np.full(20_000, 3)] * 3
    wide = reference.KeyedCounts(4).run(hot)
    narrow = reference.KeyedCounts(4, np.int16).run(hot)
    assert wide.out_val.tolist() == [60_000]
    assert narrow.out_val.tolist() != wide.out_val.tolist()


def test_imbalance_and_tables_in_force():
    assert reference.imbalance(np.array([1.0, 2.0, 3.0])) == 0.5
    assert reference.imbalance(np.zeros(3)) == 0.0
    plans = [{"interval": 2, "keys": np.array([5]), "dests": np.array([1])}]
    tables = reference.tables_in_force(4, plans)
    assert [t[0].tolist() for t in tables] == [[], [], [5], [5]]


# --- ids near 2^30, worked by hand ------------------------------------------

@pytest.mark.parametrize("spread", [1, 2**20],
                         ids=["narrow band", "wide band"])
def test_present_ids_near_2_30(spread):
    keys = BIG + spread * np.array([5, 1, 5, 9, 5, 1])
    ids, counts = reference.present(keys)
    assert ids.tolist() == (BIG + spread * np.array([1, 5, 9])).tolist()
    assert counts.tolist() == [2, 3, 1] and counts.dtype == np.int32
    empty = reference.present(np.zeros(0, np.int64))
    assert empty[0].size == 0 and empty[1].size == 0


def test_route_of_ids_near_2_30():
    ids = BIG + np.array([0, 7, 100, 2**20])
    # a table key absent from the ids is ignored
    table = (np.array([BIG + 7, BIG + 3]), np.array([4, 9]))
    want = Hash32(15, seed=7)(ids)
    want[1] = 4
    assert np.array_equal(reference.route(ids, 15, 7, table), want)


def test_windowed_counts_of_ids_near_2_30():
    a, b = BIG + 1, BIG + 2**25
    intervals = [np.array([a, a, b]), np.array([a]), np.array([b, a]),
                 np.array([a])]
    ref = reference.KeyedCounts(2).run(intervals)
    assert ref.emitted == (1 + 2) + 3 + (2 + 4) + 3 + 1
    assert ref.out_key.tolist() == [a, b]
    assert ref.out_val.tolist() == [3, 2]
    keys, slots = ref.held()
    assert keys.tolist() == [a, b] and slots.tolist() == [2, 1]


def test_controller_and_owners_over_ids_near_2_30():
    # Hash32 over 3 tasks, seed 1, sends BIG, BIG + 7, BIG + 100 to 2, 0, 1
    ids = BIG + np.array([0, 7, 100])
    assert Hash32(3, seed=1)(ids).tolist() == [2, 0, 1]
    cost = np.array([5.0, 1.0, 2.0])
    # interval 1 (loads 1, 2, 5: imbalance 0.875) plans BIG onto task 0;
    # interval 2 routes by it (loads 6, 2, 0: imbalance 1.25) but is
    # reported with every load 1 too high, and makes no plan
    plan = {"interval": 1, "keys": ids[:1], "dests": np.array([0])}
    stage = {"tasks": 3, "hash_seed": 1, "plans": [plan],
             "loads": np.array([[1.0, 2.0, 5.0], [7.0, 3.0, 1.0]]),
             "table_size": np.array([0, 1])}
    got = reference.check_controller(stage, [(ids, cost)] * 2,
                                     theta_max=0.5, table_max=1, prefix="")
    assert got == {"loads_wrong": 3, "triggers_wrong": 1, "tables_wrong": 0,
                   "plans_over": 1}
    # held: BIG for 2 intervals, on task 0 by the plan; BIG + 7 for 1, on
    # its hashed task 0. Owned: BIG right; BIG + 7 on task 1, then on task
    # 2 (owned twice, the last stands) at 8 bytes; BIG + 5, held by none
    stage["owned"] = [(ids[:1], np.array([32.0])),
                      (np.array([BIG + 7, BIG + 5]), np.array([16.0, 16.0])),
                      (np.array([BIG + 7]), np.array([8.0]))]
    got = reference.check_owners(stage, ids[:2], np.array([2, 1]),
                                 slot_bytes=16, prefix="")
    assert got == {"owners_wrong": 2 + 1, "sizes_wrong": 2}


def test_memory_does_not_grow_with_the_largest_id():
    """The same stream at ids near 0 and near 2^40 costs the same peak."""
    rng = np.random.default_rng(5)
    base = [rng.integers(0, 50_000, 200_000) for _ in range(4)]
    peaks = []
    for offset in (0, 2**40):
        tracemalloc.start()
        ref = reference.KeyedCounts(1).run([k + offset for k in base])
        ref.held()
        peaks.append(tracemalloc.get_traced_memory()[1])
        tracemalloc.stop()
    assert peaks[1] < 1.1 * peaks[0]


# --- the dense oracle: the same rules over every key of [0, domain) ---------

def dense_route(domain, tasks, seed, table):
    dest = Hash32(tasks, seed=seed)(np.arange(domain))
    dest[table[0]] = table[1]
    return dest


def dense_counts(domain, window, dtype, intervals):
    counts = [np.bincount(k, minlength=domain).astype(np.int32)
              for k in intervals]
    ever = np.zeros(domain, dtype=bool)
    out = np.zeros(domain, dtype=np.int64)
    emitted, ring = 0, []
    for c in counts:
        c = c.astype(dtype)
        w0 = np.zeros_like(c)
        for prev in ring:
            w0 = w0 + prev
        ring = (ring + [c])[-window:]
        seen = c > 0
        cs, ws = c[seen].astype(np.int64), w0[seen].astype(np.int64)
        emitted += int(np.dot(cs, ws)) + int(np.dot(cs, cs + 1) // 2)
        out[seen] = (w0 + c)[seen].astype(np.int64)
        ever |= seen
    slots = np.zeros(domain, dtype=np.int64)
    for c in counts[-window:]:
        slots += c > 0
    return counts, np.nonzero(ever)[0], out[ever], emitted, slots


def dense_checks(domain, window, dtype, intervals, stage, theta_max,
                 table_max, slot_bytes):
    counts, out_key, out_val, emitted, slots = dense_counts(
        domain, window, dtype, intervals)
    tasks, seed, plans = stage["tasks"], stage["hash_seed"], stage["plans"]
    got = reference.check_outputs(stage, types.SimpleNamespace(
        out_key=out_key, out_val=out_val, emitted=emitted), "")
    tables = reference.tables_in_force(len(stage["loads"]), plans)
    planned = {p["interval"] for p in plans}
    n = dict.fromkeys(["loads_wrong", "triggers_wrong", "tables_wrong",
                       "plans_over"], 0)
    for i, (c, table) in enumerate(zip(counts, tables), start=1):
        cost = c.astype(np.float64)
        loads = np.bincount(dense_route(domain, tasks, seed, table),
                            weights=cost, minlength=tasks)
        n["loads_wrong"] += int(np.sum(loads != stage["loads"][i - 1]))
        n["triggers_wrong"] += int((reference.imbalance(loads) > theta_max)
                                   != (i in planned))
        size = int(table[0].size)
        n["tables_wrong"] += int(size != stage["table_size"][i - 1]
                                 or size > table_max)
        if i in planned:
            p = [q for q in plans if q["interval"] == i][0]
            after = dense_route(domain, tasks, seed, (p["keys"], p["dests"]))
            n["plans_over"] += int(reference.imbalance(np.bincount(
                after, weights=cost, minlength=tasks)) > theta_max
                or p["keys"].size > table_max)
    got.update(n)
    final = ((plans[-1]["keys"], plans[-1]["dests"]) if plans
             else reference.NO_TABLE)
    held = slots > 0
    want_owner = np.where(held, dense_route(domain, tasks, seed, final), -1)
    want_size = np.where(held, slot_bytes * slots, 0.0)
    got_owner = np.full(domain, -1, dtype=np.int64)
    got_size = np.zeros(domain)
    twice = 0
    for task, (keys, sizes) in enumerate(stage["owned"]):
        twice += int(np.sum(got_owner[keys] >= 0))
        got_owner[keys] = task
        got_size[keys] = sizes
    got["owners_wrong"] = int(np.sum(got_owner != want_owner)) + twice
    got["sizes_wrong"] = int(np.sum(got_size != want_size))
    return got


def random_case(rng, domain, tasks, window, n_intervals):
    """A stream over ``[0, domain)`` and a stage's answers to it: the dense
    oracle's own, then some of each kind altered."""
    p = rng.permutation(np.arange(1, domain + 1) ** -1.2)
    intervals = [rng.choice(domain, int(rng.integers(1, 400)), p=p / p.sum())
                 for _ in range(n_intervals)]
    if rng.random() < 0.5:      # a hot key past int16 in one interval
        intervals[1] = np.r_[intervals[1], np.full(33_000, domain - 1)]
    seed = int(rng.integers(2**31))
    plans = [{"interval": int(i),
              "keys": rng.choice(domain, int(rng.integers(0, 6)),
                                 replace=False).astype(np.int64),
              "dests": rng.integers(0, tasks, 6).astype(np.int64)}
             for i in sorted(rng.choice(np.arange(1, n_intervals + 1),
                                        int(rng.integers(0, n_intervals)),
                                        replace=False))]
    for q in plans:
        q["dests"] = q["dests"][:q["keys"].size]
    tables = reference.tables_in_force(n_intervals, plans)
    counts, out_key, out_val, emitted, slots = dense_counts(
        domain, window, np.int64, intervals)
    loads = np.stack([np.bincount(dense_route(domain, tasks, seed, t),
                                  weights=c.astype(np.float64),
                                  minlength=tasks)
                      for c, t in zip(counts, tables)])
    loads[rng.random(loads.shape) < rng.choice([0, 0.05])] += 1
    table_size = np.array([t[0].size for t in tables])
    table_size[rng.random(n_intervals) < rng.choice([0, 0.1])] += 1
    final = ((plans[-1]["keys"], plans[-1]["dests"]) if plans
             else reference.NO_TABLE)
    owner = dense_route(domain, tasks, seed, final)
    held = np.flatnonzero(slots)
    owner_of = {int(k): int(owner[k]) for k in held}
    for k in rng.choice(domain, int(rng.integers(0, 4))):  # moved, added
        # or dropped keys
        owner_of[int(k)] = int(rng.integers(-1, tasks))
    owned = []
    for t in range(tasks):
        keys = np.array(sorted(k for k, o in owner_of.items() if o == t),
                        dtype=np.int64)
        sizes = 16.0 * slots[keys]
        sizes[rng.random(keys.size) < rng.choice([0, 0.05])] += 16
        owned.append((keys, sizes))
    if held.size and rng.random() < 0.5:   # a key on a second task too
        k = int(rng.choice(held))
        t = int(rng.integers(tasks))
        owned[t] = (np.r_[owned[t][0], k], np.r_[owned[t][1], 16.0])
    vals = out_val.copy()
    vals[rng.random(vals.size) < rng.choice([0, 0.05])] += 1
    keep = rng.random(out_key.size) >= rng.choice([0, 0.02])
    extra = np.arange(domain, domain + int(rng.integers(0, 2)))
    order = rng.permutation(keep.sum() + extra.size)
    stage = {"tasks": tasks, "hash_seed": seed, "plans": plans,
             "loads": loads, "table_size": table_size, "owned": owned,
             "out_keys": np.r_[out_key[keep], extra][order],
             "out_vals": np.r_[vals[keep], extra][order],
             "emitted_sum": float(emitted + int(rng.integers(0, 2)))}
    return intervals, stage


@pytest.mark.parametrize("case", range(40))
def test_sparse_reads_what_dense_reads(case):
    rng = np.random.default_rng(1000 + case)
    domain = int(rng.integers(8, 300))
    tasks = int(rng.integers(2, 6))
    window = int(rng.integers(1, 4))
    theta_max = float(rng.choice([0.05, 0.3, 1.0]))
    intervals, stage = random_case(rng, domain, tasks, window,
                                   int(rng.integers(2, 7)))
    for dtype in (np.int64, np.int16):
        want = dense_checks(domain, window, dtype, intervals, stage,
                            theta_max, 4, 16)
        ref = reference.KeyedCounts(window, dtype).run(intervals)
        got = reference.check_outputs(stage, ref, "")
        got.update(reference.check_controller(
            stage, ((i, c.astype(np.float64)) for i, c in ref.counts),
            theta_max=theta_max, table_max=4, prefix=""))
        held, slots = ref.held()
        got.update(reference.check_owners(stage, held, slots, slot_bytes=16,
                                          prefix=""))
        assert got == want, dtype


def test_refcost_checks_answers_built_from_the_reference():
    """``refcost.py`` at Beam's 10,000 events a second: the answers it
    builds from the reference read 0 where they follow the rules it sets
    (its plans move hot keys, they need not balance)."""
    out = refcost.measure(10_000, 3, 2, 7)
    # 3,000 auctions an interval: interval 4 ends at id 15,000 + 1,000
    assert 15_000 < out["max_id"] <= 16_010 and out["reference_s"] > 0
    assert {k: v for k, v in out["checks"].items()
            if k not in ("triggers_wrong", "plans_over")} == dict.fromkeys(
        ["outputs_wrong", "emitted_gap", "loads_wrong", "tables_wrong",
         "owners_wrong", "sizes_wrong"], 0)

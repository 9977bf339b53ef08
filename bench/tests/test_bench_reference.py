"""The plain reference on hand-worked cases."""

import numpy as np

import reference
from repro.core.balancer.hashing import Hash32


def test_route_is_the_hash_with_table_overrides():
    keys = np.arange(5000, dtype=np.int64)
    table = (np.array([3, 17]), np.array([14, 0]))
    dest = reference.route(reference.hashed(5000, 15, 7), table)
    want = Hash32(15, seed=7)(keys)
    want[[3, 17]] = [14, 0]
    assert np.array_equal(dest, want)


def test_windowed_counts_by_hand():
    # window 2: a tuple of interval i counts its key over intervals i-2..i
    intervals = [np.array([1, 1, 2]), np.array([1]), np.array([2, 1]),
                 np.array([1])]
    ref = reference.KeyedCounts(4, 2).run(intervals)
    # key 1 emits 1,2 | 3 | 4 | 3 (interval 1 left the window); key 2: 1 | 2
    assert ref.emitted == (1 + 2) + 3 + (2 + 4) + 3 + 1
    assert ref.out_key.tolist() == [1, 2]
    assert ref.out_val.tolist() == [3, 2]
    keys, slots = ref.held()
    assert keys.tolist() == [1, 2] and slots.tolist() == [2, 1]


def test_narrow_window_arithmetic_wraps():
    hot = [np.full(20_000, 3)] * 3
    wide = reference.KeyedCounts(8, 4).run(hot)
    narrow = reference.KeyedCounts(8, 4, np.int16).run(hot)
    assert wide.out_val.tolist() == [60_000]
    assert narrow.out_val.tolist() != wide.out_val.tolist()


def test_imbalance_and_tables_in_force():
    assert reference.imbalance(np.array([1.0, 2.0, 3.0])) == 0.5
    assert reference.imbalance(np.zeros(3)) == 0.0
    plans = [{"interval": 2, "keys": np.array([5]), "dests": np.array([1])}]
    tables = reference.tables_in_force(4, plans)
    assert [t[0].tolist() for t in tables] == [[], [], [5], [5]]

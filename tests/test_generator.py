"""``hot_set_drift_trace``: open-loop, seeded traffic whose hot set moves."""

import numpy as np
import pytest

from repro.streams import hot_set_drift_trace


def _trace(**kw):
    args = dict(domain=1 << 14, z=0.9, tuples=50_000, intervals=5, hot=32,
                shift_every=2, seed=3)
    args.update(kw)
    return hot_set_drift_trace(**args)


def _top(keys, n):
    uniq, counts = np.unique(keys, return_counts=True)
    return set(uniq[np.argsort(-counts, kind="stable")[:n]].tolist())


def test_trace_is_a_function_of_the_seed():
    a, b, c = _trace(), _trace(), _trace(seed=4)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    for keys in a:
        assert keys.dtype == np.int64 and keys.shape == (50_000,)
        assert keys.min() >= 0 and keys.max() < 1 << 14


def test_hot_set_moves_only_at_shift_boundaries():
    trace = _trace()
    hot = [_top(keys, 4) for keys in trace]
    assert hot[0] == hot[1]                  # same epoch: same hot keys
    assert hot[2] == hot[3]
    assert not hot[1] & hot[2]               # intervals 2 and 4 move them
    assert not hot[3] & hot[4]


def test_head_share_follows_zipf():
    keys = _trace(intervals=1, tuples=200_000)[0]
    ranks = np.arange(1, (1 << 14) + 1, dtype=np.float64) ** -0.9
    expect = ranks[:1].sum() / ranks.sum()
    top = np.unique(keys, return_counts=True)[1].max() / keys.size
    assert abs(top - expect) < 0.01


@pytest.mark.parametrize("hot", [0, 1 << 14])
def test_rejects_a_hot_set_outside_the_domain(hot):
    with pytest.raises(ValueError, match="hot"):
        _trace(hot=hot)

"""The traffic generator: the paper's fluctuation rule, open loop, and a
function of the seed and the mix alone."""

import json

import numpy as np
import pytest

import core
import reference
import source

CFG = json.loads((core.BENCH / "configs" / "paper-wordcount.json")
                 .read_text())


def loads(freq, dest, tasks):
    return np.bincount(dest, weights=freq, minlength=tasks)


@pytest.mark.parametrize("f", [0.3, 1.0])
def test_fluctuation_stops_at_the_first_swap_that_reaches_f(f):
    rng = np.random.default_rng(3)
    freq = rng.permutation(np.arange(1, 5001) ** -0.85)
    dest = reference.hashed(5000, 15, 0)
    before = loads(freq, dest, 15)
    total = freq.sum()
    made = source.fluctuate(freq, dest, 15, f, 200_000, rng)
    change = np.abs(loads(freq, dest, 15) - before) / before
    assert made > 0
    assert change.max() >= f
    assert freq.sum() == pytest.approx(total, rel=1e-12)   # a permutation


def test_fluctuation_gives_up_after_max_swaps():
    rng = np.random.default_rng(4)
    freq = np.ones(1000)
    dest = reference.hashed(1000, 15, 0)
    # equal frequencies: no swap changes a load, so f is never reached
    assert source.fluctuate(freq, dest, 15, 0.5, 777, rng) <= 777
    assert np.array_equal(freq, np.ones(1000))


def test_the_layout_not_the_seed_decides_the_frequencies():
    kw = dict(f=1.0, tasks=15, hash_seed=0, max_swaps=200_000)
    a = source.frequencies(2000, 0.85, 4, layout_seed=7, **kw)
    b = source.frequencies(2000, 0.85, 4, layout_seed=7, **kw)
    c = source.frequencies(2000, 0.85, 4, layout_seed=8, **kw)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.allclose(a.sum(axis=1), 1.0)
    assert not np.array_equal(a[0], a[1])              # it fluctuates
    still = source.frequencies(2000, 0.85, 3, layout_seed=7,
                               **dict(kw, f=0.0))
    assert np.array_equal(still[0], still[2])


def test_same_seed_same_keys_and_large_seeds_work():
    mix = source.Mix.load("drift.sat")
    cfg = dict(CFG, tuples=50_000)
    a = source.traffic(cfg, mix, 3, 2**33 + 5)
    b = source.traffic(cfg, mix, 3, 2**33 + 5)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], source.traffic(cfg, mix, 1,
                                                   2**33 + 6)[0])
    assert all(k.dtype == np.int64 and k.min() >= 0
               and k.max() < CFG["domain"] and k.size == 50_000 for k in a)


def test_tuples_follow_the_interval_frequencies():
    probs = np.zeros((2, 10))
    probs[0, 3], probs[1, [1, 8]] = 1.0, 0.5
    a, b = source.draw(probs, 10_000, seed=1)
    assert set(a.tolist()) == {3} and set(b.tolist()) == {1, 8}


def test_mix_files_load():
    mix = source.Mix.load("drift.sat")
    assert mix.window_intervals(10, CFG["tuples"]) >= 1

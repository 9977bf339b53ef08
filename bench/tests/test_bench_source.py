"""The traffic generator: the paper's fluctuation rule, open loop, and a
function of the seed and the mix alone."""

import dataclasses
import hashlib
import json

import numpy as np
import pytest

import core
import reference
import source
import tiny

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


#: sha256 of the bytes of the first three intervals of the tiny cell's
#: traffic, as the generator drew them when the cell was first measured:
#: the cell measures the same work for as long as these hold
DIGESTS = {
    2**31 + 17:
    "e2bc17a4fd9ab58cd09e246bd99fceb6c57ceea61534b6bbc76c787d510575b5",
    2**33 + 5:
    "82d59c6ae0c281871d2fc94072180117276d05c7390574419f4efd176a82c18d",
}


@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_traffic_is_byte_identical_to_the_first_measured(seed):
    cfg = dict(CFG, **tiny.TINY)
    assert core.traffic_source(cfg) is source
    mix = dataclasses.replace(source.Mix.load("drift.sat"), **tiny.TRAFFIC)
    digest = hashlib.sha256()
    for keys in source.traffic(cfg, mix, 3, seed):
        assert keys.dtype == np.int64 and keys.size == cfg["tuples"]
        digest.update(keys.tobytes())
    assert digest.hexdigest() == DIGESTS[seed]


def test_mix_keys_beyond_the_harness_reach_the_generator():
    mix = source.Mix.load("drift.sat")
    assert (mix.warmup_intervals, mix.layout_seed) == (6, 20161018)
    assert mix.params["f"] == 1.0 and mix.params["max_swaps"] == 200_000
    assert not {"warmup_intervals", "layout_seed", "pool_rate"} \
        & set(mix.params)

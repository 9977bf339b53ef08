"""NEXmark's bid generator (``sources/nexmark.py``) against the rules of
Beam's ``AuctionGenerator``/``BidGenerator``, and a configuration that
names it driving a cell end to end, by overrides or by a sizes file."""

import json

import numpy as np
import pytest

import tiny
from sources import nexmark

#: Beam's NexmarkConfiguration defaults, 10,000 events a second
BEAM = {"first_event_rate": 10_000, "window_period_sec": 5,
        "hot_auction_ratio": 2, "num_in_flight_auctions": 100,
        "person_proportion": 1, "auction_proportion": 3,
        "bid_proportion": 46, "tuples": 46_000}
PROPS = (1, 3, 46)


def test_event_kinds_by_offset_in_each_epoch():
    """Offset 0 of every 50 event ids is a person, 1-3 auctions, 4-49
    bids: only bids are counted, and each auction names the next id."""
    events = np.arange(3 * 50)
    is_bid = np.array([nexmark.bids_before(e + 1, *PROPS)
                       - nexmark.bids_before(e, *PROPS) for e in events])
    assert np.array_equal(is_bid, (events % 50 >= 4).astype(int))
    auctions = events[(events % 50 >= 1) & (events % 50 <= 3)]
    assert np.array_equal(
        nexmark.last_base0_auction_id(auctions, *PROPS),
        np.arange(auctions.size))


def test_last_base0_auction_id_by_hand():
    """Worked from ``lastBase0AuctionId`` for ids 0-120: a person looks
    back to the previous epoch's last auction (-1 before any), an auction
    names itself, a bid its epoch's last auction."""
    want = ([-1, 0, 1, 2] + [2] * 46
            + [2, 3, 4, 5] + [5] * 46
            + [5, 6, 7, 8] + [8] * 17)
    got = nexmark.last_base0_auction_id(np.arange(121), *PROPS)
    assert got.tolist() == want


def _interval(j, seed=2**33 + 7):
    cfg = dict(BEAM)
    keys = nexmark.traffic(cfg, None, j + 1, seed)[j]
    events = np.arange(nexmark.first_event(cfg, j),
                       nexmark.first_event(cfg, j + 1))
    events = events[events % 50 >= 4]
    last = nexmark.last_base0_auction_id(events, *PROPS)
    return keys, last


@pytest.mark.parametrize("j", [0, 9])
def test_hot_share_and_in_flight_ranges(j):
    keys, last = _interval(j)
    hot = last // 100 * 100 + nexmark.FIRST_AUCTION_ID
    low = np.maximum(last - 100, 0) + nexmark.FIRST_AUCTION_ID
    high = last + 10 + nexmark.FIRST_AUCTION_ID
    # a bid is hot with probability 1/2, and a cold one lands on the hot
    # auction with probability 1 / (its range's size)
    p = 0.5 + 0.5 / (high - low + 1)
    on_hot = keys == hot
    assert abs(on_hot.sum() - p.sum()) <= 5 * np.sqrt(np.sum(p * (1 - p)))
    cold = keys[~on_hot]
    assert np.all((cold >= low[~on_hot]) & (cold <= high[~on_hot]))
    # the whole range is drawn from, its lead of 10 ids included
    assert np.any(cold == high[~on_hot]) and np.any(cold == low[~on_hot])


@pytest.mark.parametrize("rate,bids", [(10_000, 46_000), (100_000, 460_000),
                                       (20_000, 92_000)])
def test_interval_sizes_are_exact(rate, bids):
    cfg = dict(BEAM, first_event_rate=rate, tuples=bids)
    assert [k.size for k in nexmark.traffic(cfg, None, 3, 5)] == [bids] * 3
    with pytest.raises(ValueError):
        nexmark.traffic(dict(cfg, tuples=bids + 1), None, 1, 5)


def test_uneven_intervals_are_refused():
    # 3,000 events a second is a 333 us delay: 15,015 or 15,016 events
    # a period
    cfg = dict(BEAM, first_event_rate=3_000, tuples=13_815)
    with pytest.raises(ValueError):
        nexmark.traffic(cfg, None, 4, 5)


def test_seed_decides_the_draws_and_each_interval_its_own():
    a = nexmark.traffic(BEAM, None, 3, 2**33 + 11)
    b = nexmark.traffic(BEAM, None, 5, 2**33 + 11)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    c = nexmark.traffic(BEAM, None, 3, 2**33 + 12)
    assert not np.array_equal(a[2], c[2])
    assert all(k.dtype == np.int64 for k in a)


def test_a_configuration_naming_nexmark_runs_a_cell():
    """The count pipeline fed by NEXmark's bids through ``core.run_cell``
    on the CPU is correct. At 10,000 events a second the hottest auction
    holds ~2% of an interval's bids, under the mean task's 1/15, so a plan
    can meet theta_max; the key domain covers the ids seven intervals
    reach."""
    overrides = dict(BEAM, traffic_source="nexmark", domain=2**15)
    result, run = tiny.run("wordcount.drift.sat", overrides=overrides,
                           traffic_overrides={"pool_rate": 46_000})
    assert result["correct"], result["checks"]
    assert result["attempted"] == 46_000 * len(run.intervals)


def test_a_sizes_file_switches_a_cell_to_nexmark(tmp_path, monkeypatch):
    """A sizes file for the cell's configuration, found by its name, sets
    the tiny run: here NEXmark's bids at Beam's 10,000 events a second in
    place of the default sizes."""
    sizes = {"overrides": dict(BEAM, traffic_source="nexmark",
                               domain=2**15),
             "traffic": {"warmup_intervals": 4, "pool_rate": 46_000}}
    (tmp_path / "paper-wordcount.json").write_text(json.dumps(sizes))
    monkeypatch.setattr(tiny, "SIZES", tmp_path)
    result, run = tiny.run("wordcount.drift.sat")
    assert result["correct"], result["checks"]
    assert run.mix.warmup_intervals == 4
    assert result["attempted"] == 46_000 * len(run.intervals)

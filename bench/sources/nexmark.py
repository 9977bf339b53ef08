"""NEXmark's bid stream: the auction of each bid, as Beam's generator draws it.

A configuration with ``"traffic_source": "nexmark"`` is fed by this module.
It transcribes into numpy the rules by which Apache Beam's NEXmark generator
(``org.apache.beam.sdk.nexmark.sources.generator``: ``GeneratorConfig``,
``model.AuctionGenerator``, ``model.BidGenerator``) picks the one field of a
bid that Query 5 reads, its auction id:

* event ``e`` is a person, an auction or a bid by its offset ``e % total``
  in an epoch of ``total = person + auction + bid`` proportions (1:3:46 by
  default): the first ``person_proportion`` offsets are persons, the next
  ``auction_proportion`` auctions, the rest bids;
* :func:`last_base0_auction_id` is the base-0 id of the last auction made
  at or before the event (``AuctionGenerator.lastBase0AuctionId``);
* with probability ``1 - 1/hot_auction_ratio`` a bid goes to the hot
  auction ``(last // 100) * 100``, which moves every 100 auctions;
  otherwise to one drawn uniformly from ``[max(last -
  num_in_flight_auctions, 0), last + 10]`` (``nextBase0AuctionId``, with
  Beam's lead of 10 auction ids);
* ``FIRST_AUCTION_ID`` (1000) is added.

Event time runs at a constant ``first_event_rate`` events a second: event
``e`` is stamped ``floor(e * d / 1000)`` ms with ``d`` Beam's inter-event
delay, ``10^6 / first_event_rate`` µs rounded to the nearest integer.
Interval ``j`` holds the bids stamped in ``[j P, (j + 1) P)`` with ``P`` =
``window_period_sec``, the step of Query 5's sliding window, in event
order. Every parameter comes from the configuration, under the snake-case
name of its field of Beam's ``NexmarkConfiguration``; the configuration's
``tuples`` must equal the bids of every interval. The mix is not read:
every seed offers the same intervals of the same sizes, the same hot
auctions and the same in-flight ranges, and the seed draws only which bid
goes where. Each interval draws from its own child of the seed.

Departures from Beam:

* Java draws each event from its own ``new Random(eventId)``; those streams
  are not reproduced bit for bit: the draws follow the same rules from
  numpy's generator;
* one generator makes the whole stream at ``first_event_rate``, with event
  ids from 0; Beam may split it over ``num_event_generators`` sources, each
  with a range of event ids of its own;
* the occasional delays (``occasional_delay_sec``, ``prob_delayed_event``)
  and out-of-order groups (``out_of_order_group_size``) are left out: the
  engine has no event time (ROADMAP B.1), so bids arrive in event order;
* the records of persons and auctions are not built: Query 5 reads only
  bids.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import List, Tuple

import numpy as np

FIRST_AUCTION_ID = 1000     # GeneratorConfig.FIRST_AUCTION_ID
AUCTION_ID_LEAD = 10        # GeneratorConfig.AUCTION_ID_LEAD
HOT_AUCTION_BATCH = 100     # BidGenerator.HOT_AUCTION_RATIO
US_PER_SECOND = 1_000_000


def proportions(cfg: dict) -> Tuple[int, int, int]:
    return (int(cfg["person_proportion"]), int(cfg["auction_proportion"]),
            int(cfg["bid_proportion"]))


def last_base0_auction_id(event_ids: np.ndarray, person: int,
                          auction: int, bid: int) -> np.ndarray:
    """``AuctionGenerator.lastBase0AuctionId`` of each event id: a person
    looks back to the last auction of the previous epoch, a bid to the last
    of its own, an auction names itself."""
    epoch, offset = np.divmod(np.asarray(event_ids, np.int64),
                              person + auction + bid)
    is_person = offset < person
    is_bid = offset >= person + auction
    epoch = np.where(is_person, epoch - 1, epoch)
    offset = np.where(is_person | is_bid, auction - 1, offset - person)
    return epoch * auction + offset


def first_event(cfg: dict, interval: int) -> int:
    """The first event id stamped at or after ``interval`` periods."""
    rate = int(cfg["first_event_rate"])
    delay_us = (US_PER_SECOND + rate // 2) // rate  # RateUnit.rateToPeriodUs
    start_us = interval * int(cfg["window_period_sec"]) * US_PER_SECOND
    return -(-start_us // delay_us)


def bids_before(event_id: int, person: int, auction: int, bid: int) -> int:
    """How many of the events ``[0, event_id)`` are bids."""
    epoch, offset = divmod(event_id, person + auction + bid)
    return epoch * bid + max(0, offset - person - auction)


def bids(cfg: dict, interval: int, rng: np.random.Generator) -> np.ndarray:
    """The auction ids of interval ``interval``'s bids, in event order."""
    person, auction, bid = proportions(cfg)
    events = np.arange(first_event(cfg, interval),
                       first_event(cfg, interval + 1), dtype=np.int64)
    events = events[events % (person + auction + bid) >= person + auction]
    last = last_base0_auction_id(events, person, auction, bid)
    hot = rng.integers(int(cfg["hot_auction_ratio"]), size=events.size) > 0
    low = np.maximum(last - int(cfg["num_in_flight_auctions"]), 0)
    cold = low + rng.integers(0, last - low + 1 + AUCTION_ID_LEAD)
    return np.where(hot, last // HOT_AUCTION_BATCH * HOT_AUCTION_BATCH,
                    cold) + FIRST_AUCTION_ID


def traffic(cfg: dict, mix, intervals: int, seed: int
            ) -> List[np.ndarray]:
    """The auction ids of the bids of intervals ``0 .. intervals - 1``;
    raises ``ValueError`` where an interval does not hold ``cfg["tuples"]``
    bids."""
    props = proportions(cfg)
    for j in range(intervals):
        n = (bids_before(first_event(cfg, j + 1), *props)
             - bids_before(first_event(cfg, j), *props))
        if n != cfg["tuples"]:
            raise ValueError(f"interval {j} holds {n} bids, the "
                             f"configuration's tuples is {cfg['tuples']}")
    seqs = np.random.SeedSequence(int(seed)).spawn(intervals)

    def one(j: int) -> np.ndarray:
        return bids(cfg, j, np.random.default_rng(seqs[j]))

    with ThreadPoolExecutor(max_workers=8) as pool:
        return list(pool.map(one, range(intervals)))

#!/usr/bin/env python3
"""The plain reference's cost on NEXmark's bid stream, on the host's CPU.

    python3 bench/refcost.py --rate 1000000 --intervals 100 --first 0

draws intervals ``first .. first + intervals - 1`` of ``sources/nexmark.py``
at ``rate`` events a second (Beam's other defaults), one at a time, and
runs the ``count`` pipeline's reference over them: the windowed counts,
then the eight numbers against answers built from the reference itself
(its outputs, the loads under a table of the ``table_max`` hottest keys of
each interval, the owners under the last table). Prints one JSON line: the
seconds of the reference (the drawing left out), the peak resident set,
the largest id and the eight numbers. A later ``first`` gives the same
stream at larger ids, so the peak shows whether memory follows them.
"""

import argparse
import importlib.util
import json
import resource
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import reference  # noqa: E402
from sources import nexmark  # noqa: E402

#: Beam's NexmarkConfiguration defaults but the rate, and the stage of
#: ``configs/paper-wordcount.json``
CFG = {"window_period_sec": 5, "hot_auction_ratio": 2,
       "num_in_flight_auctions": 100, "person_proportion": 1,
       "auction_proportion": 3, "bid_proportion": 46, "tasks": 15,
       "hash_seed": 0, "window": 1, "theta_max": 0.08, "table_max": 3000,
       "state_bytes_per_slot": 16}


def count_reference():
    path = BENCH / "pipelines" / "count.reference.py"
    spec = importlib.util.spec_from_file_location("count_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def measure(rate: int, intervals: int, first: int, seed: int) -> dict:
    cfg = dict(CFG, first_event_rate=rate)
    seqs = np.random.SeedSequence(seed).spawn(first + intervals)
    drawn = {"s": 0.0, "max_id": 0}

    def stream():
        for j in range(first, first + intervals):
            t = time.perf_counter()
            keys = nexmark.bids(cfg, j, np.random.default_rng(seqs[j]))
            drawn["s"] += time.perf_counter() - t
            drawn["max_id"] = max(drawn["max_id"], int(keys.max()))
            yield keys

    t = time.perf_counter()
    ref = reference.KeyedCounts(cfg["window"]).run(stream())
    # answers from the reference: each interval plans its hottest keys
    # onto the next task, and the next interval routes by that table
    plans = []
    for i, (ids, c) in enumerate(ref.counts, start=1):
        hot = np.sort(ids[np.argsort(c)[-cfg["table_max"]:]])
        dests = (reference.route(hot, cfg["tasks"], cfg["hash_seed"],
                                 reference.NO_TABLE) + 1) % cfg["tasks"]
        plans.append({"interval": i, "keys": hot, "dests": dests})
    tables = reference.tables_in_force(len(ref.counts), plans)
    loads = np.stack([np.bincount(
        reference.route(ids, cfg["tasks"], cfg["hash_seed"], table),
        weights=c.astype(np.float64), minlength=cfg["tasks"])
        for (ids, c), table in zip(ref.counts, tables)])
    held, slots = ref.held()
    owner = reference.route(held, cfg["tasks"], cfg["hash_seed"],
                            (plans[-1]["keys"], plans[-1]["dests"]))
    stage = {"tasks": cfg["tasks"], "hash_seed": cfg["hash_seed"],
             "plans": plans, "loads": loads,
             "table_size": np.r_[0, [p["keys"].size for p in plans[:-1]]],
             "owned": [(held[owner == k], 16.0 * slots[owner == k])
                       for k in range(cfg["tasks"])],
             **count_reference().counted_answers(ref)}
    checks = count_reference().check_count_stage(cfg, stage, ref, "")
    seconds = time.perf_counter() - t - drawn["s"]
    return {"rate": rate, "intervals": intervals, "first": first,
            "max_id": drawn["max_id"], "reference_s": seconds,
            "draw_s": drawn["s"],
            "peak_rss_mb": resource.getrusage(resource.RUSAGE_SELF)
            .ru_maxrss / 1024, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rate", type=int, default=1_000_000)
    ap.add_argument("--intervals", type=int, default=100)
    ap.add_argument("--first", type=int, default=0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    print(json.dumps(measure(args.rate, args.intervals, args.first,
                             args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

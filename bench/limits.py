#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/limits.py --workload <cell> --seeds 11 12 13 --seconds 10

runs the cell once per seed in one process, at its own size and load, and
prints per seed one JSON line with the numbers compared for the program
(``program``: a sound run reads 0 in each) and for the control, the
reference's own answers kept in a narrower integer type and put in the
program's place (``control``: it must read above the limit in at least
one). The benchmark's own runs never run the control.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import core  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        t = time.perf_counter()
        r, _ = core.run_cell(args.workload, seed, args.seconds, False,
                          t_start=t, control=True)
        print(json.dumps({
            "seed": seed, "correct": r["correct"], "metrics": r["metrics"],
            "program": {k: v["value"] for k, v in r["checks"].items()},
            "control": r["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
                         --trace <0|1>

from the root of a checkout that holds the program under ``src/``. The
last line of standard output is the result, one JSON object; the numbers
compared with the plain reference are the last lines of standard error.
Without a TPU of a known kind, or without the program, it exits non-zero
and prints no result. See ``core.py`` for what a run does.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True,
                    help="seed of the cell's traffic")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace the window and report per-layer metrics")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program at {ROOT / 'src' / 'repro'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import core
    import repro.streams
    where = Path(repro.streams.__file__).resolve()
    if not where.is_relative_to(ROOT / "src"):
        print(f"repro is imported from {where}, not this checkout",
              file=sys.stderr)
        return 2
    try:
        result, _ = core.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except core.Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

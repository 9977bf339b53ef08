"""A cell cut to a size a CPU test holds, run in-process without the chip
check: the configuration's own key domain, and enough tuples per interval
that the hottest key's count in one interval passes 2^15, where the
control's int16 wraps."""

import json
import time

import core

CELLS = [w["name"] for w in json.loads(
    (core.ROOT / "BENCHMARK.json").read_text())["workloads"]]
TINY = {"tuples": 700_000}
TRAFFIC = {"warmup_intervals": 4, "pool_rate": 3_000_000}


def run(cell: str, seed: int = 2**31 + 17, seconds: float = 2.0,
        trace: bool = False, control: bool = False,
        overrides: dict = None, traffic_overrides: dict = None) -> tuple:
    """``core.run_cell``'s result line and run of the tiny cell, with
    ``overrides``/``traffic_overrides`` on top of the tiny sizes."""
    return core.run_cell(cell, seed, seconds, trace,
                         t_start=time.perf_counter(), require_tpu=False,
                         overrides=dict(TINY, **(overrides or {})),
                         traffic_overrides=dict(TRAFFIC,
                                                **(traffic_overrides or {})),
                         control=control)

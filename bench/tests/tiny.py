"""A cell cut to a size a CPU test holds, run in-process without the chip
check.

A configuration gives its cells' tiny sizes in ``sizes/<config>.json``:
``{"overrides": {...}, "traffic": {...}}``, put over the configuration's
own values and its mix's. Without that file a cell runs at ``TINY`` and
``TRAFFIC``: the configuration's own key domain, and enough tuples per
interval that the hottest key's count in one interval passes 2^15, where
the control's int16 wraps."""

import json
import time
from pathlib import Path

import core

WORKLOADS = {w["name"]: w for w in json.loads(
    (core.ROOT / "BENCHMARK.json").read_text())["workloads"]}
CELLS = list(WORKLOADS)
SIZES = Path(__file__).resolve().parent / "sizes"
TINY = {"tuples": 700_000}
TRAFFIC = {"warmup_intervals": 4, "pool_rate": 3_000_000}


def sizes(cell: str) -> tuple:
    """``(overrides, traffic_overrides)`` of the tiny cell: its
    configuration's sizes file, else ``TINY`` and ``TRAFFIC``."""
    path = SIZES / f"{WORKLOADS[cell]['config']}.json"
    if not path.exists():
        return TINY, TRAFFIC
    raw = json.loads(path.read_text())
    return raw["overrides"], raw["traffic"]


def run(cell: str, seed: int = 2**31 + 17, seconds: float = 2.0,
        trace: bool = False, control: bool = False,
        overrides: dict = None, traffic_overrides: dict = None) -> tuple:
    """``core.run_cell``'s result line and run of the tiny cell, with
    ``overrides``/``traffic_overrides`` on top of the tiny sizes."""
    tiny, traffic = sizes(cell)
    return core.run_cell(cell, seed, seconds, trace,
                         t_start=time.perf_counter(), require_tpu=False,
                         overrides=dict(tiny, **(overrides or {})),
                         traffic_overrides=dict(traffic,
                                                **(traffic_overrides or {})),
                         control=control)

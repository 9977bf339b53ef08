"""Plain reference of the ``count`` pipeline (numpy only)."""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

from reference import (KeyedCounts, check_controller, check_outputs,
                       check_owners)

#: the control's arithmetic: the integer type one step below the int32 the
#: configuration's state is exact in
CONTROL_DTYPE = np.int16


def check_count_stage(cfg: dict, stage: dict, ref: KeyedCounts,
                      prefix: str) -> Dict[str, float]:
    out = check_outputs(stage, ref, prefix)
    out.update(check_controller(
        stage, ((ids, c.astype(np.float64)) for ids, c in ref.counts),
        theta_max=cfg["theta_max"], table_max=cfg["table_max"],
        prefix=prefix))
    held, slots = ref.held()
    out.update(check_owners(stage, held, slots,
                            slot_bytes=cfg["state_bytes_per_slot"],
                            prefix=prefix))
    return out


def counted_answers(ref: KeyedCounts) -> dict:
    """The answers a count stage hands back, as the reference works them
    out: per-key outputs and the sum of emits."""
    return {"out_keys": ref.out_key, "out_vals": ref.out_val,
            "emitted_sum": float(ref.emitted)}


def check(cfg: dict, intervals: Sequence[np.ndarray],
          observed: dict) -> Dict[str, float]:
    """Numbers that are 0 on a sound run."""
    ref = KeyedCounts(cfg["window"]).run(intervals)
    return check_count_stage(cfg, observed["count"], ref, "")


def control(cfg: dict, intervals: Sequence[np.ndarray],
            observed: dict) -> dict:
    """The reference in the program's place, its window kept in
    ``CONTROL_DTYPE``; routing and plans are the program's own."""
    ref = KeyedCounts(cfg["window"], CONTROL_DTYPE).run(intervals)
    return {"count": dict(observed["count"], **counted_answers(ref))}

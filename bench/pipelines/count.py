"""Windowed WordCount: one ``KeyedStage`` on the one-chip device path, fed
through ``process_interval_arrays``; the warm-up of its route shapes, and
what is read back from it for the comparison with the plain reference."""

from __future__ import annotations

import numpy as np

from repro.core.balancer.hashing import Hash32
from repro.kernels.routing_lookup import routing_lookup
from repro.streams import WordCount, keyed_stage

#: every routing-table capacity a stage can reach below ``table_max`` (the
#: stage pads its table to a power of two, at least 128)
TABLE_CAPACITIES = (128, 256, 512, 1024, 2048, 4096)


def device_stage(cfg: dict, operator, hash_seed: int, interpret: bool):
    """The public entry, on the device backend with the Pallas route."""
    stage = keyed_stage(operator, cfg["tasks"], cfg["theta_max"],
                        table_max=cfg["table_max"], window=cfg["window"],
                        seed=hash_seed, hash_cls=Hash32,
                        state_backend="device", substrate="pallas",
                        kernel_interpret=interpret)
    if stage.state_backend != "device":
        raise RuntimeError(f"stage resolved to {stage.state_backend!r}")
    return stage


def route_domain(max_key: int) -> int:
    """The dense domain a stage grows to for keys up to ``max_key``: a
    power of two above it, at least 512."""
    return max(512, 1 << int(max_key).bit_length())


def warm_routes(cfg: dict, hash_seed: int, domain: int,
                interpret: bool) -> None:
    """Compile the dense route for every table capacity up to
    ``table_max``, over a ``domain`` of keys, with the arguments the stage
    passes (so the window finds them compiled)."""
    import jax.numpy as jnp
    keys = jnp.arange(domain + 1, dtype=jnp.int32)
    top = max(128, 1 << max(0, cfg["table_max"] - 1).bit_length())
    for cap in TABLE_CAPACITIES:
        if cap > top:
            break
        tk = jnp.asarray(np.full(cap, -1, np.int32))
        td = jnp.asarray(np.zeros(cap, np.int32))
        routing_lookup(keys, tk, td, cfg["tasks"], seed=hash_seed,
                       interpret=interpret).block_until_ready()


def table_capacity(stage) -> int:
    """The padded table capacity the stage's route runs at: the power of
    two (at least 128) above the largest table it has held."""
    top = int(max((r.table_size for r in stage.reports), default=0))
    return max(128, 1 << max(0, top - 1).bit_length())


def plan_seconds(stage, first_interval: int, last_interval: int) -> float:
    """Seconds the controller planned in intervals ``first..last``."""
    return float(sum(ev.result.plan_time_s
                     for ev in stage.controller.history
                     if ev.triggered
                     and first_interval <= ev.interval <= last_interval))


def observe(stage) -> dict:
    """Everything the reference compares, as plain arrays."""
    plans = []
    for ev in stage.controller.history:
        if ev.triggered:
            table = ev.result.assignment.table
            plans.append({
                "interval": ev.interval,
                "keys": np.fromiter(table.keys(), np.int64, len(table)),
                "dests": np.fromiter(table.values(), np.int64, len(table))})
    out = stage.outputs
    return {
        "tasks": stage.n_tasks,
        "hash_seed": stage.controller.assignment.hash_router.seed,
        "loads": np.stack([np.asarray(r.task_loads, np.float64)
                           for r in stage.reports]),
        "table_size": np.asarray([r.table_size for r in stage.reports],
                                 dtype=np.int64),
        "plans": plans,
        "owned": [s.sizes_arrays() for s in stage.stores],
        "out_keys": np.fromiter(out.keys(), np.int64, len(out)),
        "out_vals": np.fromiter(out.values(), np.int64, len(out)),
        "emitted_sum": stage.emitted_sum,
    }


class System:
    """The system under test for one configuration."""

    def __init__(self, cfg: dict, interpret: bool):
        self.cfg = cfg
        self.interpret = interpret
        self.stage = device_stage(
            cfg, WordCount(bytes_per_entry=cfg["state_bytes_per_slot"]),
            cfg["hash_seed"], interpret)
        self.domain = 0

    def process(self, keys: np.ndarray) -> None:
        self.stage.process_interval_arrays(keys)

    def warm(self, max_key: int) -> None:
        self.domain = route_domain(max_key)
        warm_routes(self.cfg, self.cfg["hash_seed"], self.domain,
                    self.interpret)

    def plan_seconds(self, first: int, last: int) -> float:
        return plan_seconds(self.stage, first, last)

    def kernel_shapes(self) -> dict:
        """Shapes of the window's kernel calls, for their work functions."""
        return {"interval_step_add": {"window": self.cfg["window"],
                                      "keys": self.domain + 1},
                "routing_lookup": {"keys": self.domain + 1,
                                   "table": table_capacity(self.stage)}}

    def observe(self) -> dict:
        return {"count": observe(self.stage)}

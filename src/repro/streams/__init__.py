"""Faithful stream-processing substrate: engine, operators, state, generator,
pluggable state backends, multi-stage topologies, and checkpointed recovery
with deterministic failure injection."""

from .backends import (BACKENDS, ColumnarBackend, DeviceBackend,
                       ObjectBackend, StateBackend, register_backend)
from .checkpoint import (CheckpointStore, StageCheckpoint, TopologyCheckpoint,
                         checkpoint_stage, checkpoint_topology, restore_stage,
                         restore_topology)
from .engine import STATE_BACKENDS, SUBSTRATES, IntervalReport, KeyedStage
from .faults import (ChaosRunner, DropDelivery, DuplicateDelivery, FaultPlan,
                     FaultInjector, KillTask, RecoveryEvent, StallTask,
                     TaskKilled, TaskStalled)
from .generator import WorkloadGen, hot_set_drift_trace, zipf_frequencies
from .operators import (BatchResult, Filter, IntervalBatchResult, MergeCounts,
                        Operator, PartialWordCount, WindowedSelfJoin,
                        WordCount)
from .state import (ColumnarSpec, ColumnarStateStore, KeyState,
                    TaskStateStore)
from .topology import (StageSpec, Topology, TopologyReport, keyed_stage,
                       router_merge_topology)

__all__ = [
    "STATE_BACKENDS", "SUBSTRATES", "IntervalReport", "KeyedStage",
    "WorkloadGen", "hot_set_drift_trace", "zipf_frequencies", "BatchResult",
    "Filter",
    "IntervalBatchResult", "MergeCounts", "Operator", "PartialWordCount",
    "WindowedSelfJoin", "WordCount", "ColumnarSpec", "ColumnarStateStore",
    "KeyState", "TaskStateStore", "StageSpec", "Topology", "TopologyReport",
    "keyed_stage", "router_merge_topology", "DeviceStateFleet",
    "DeviceTaskView",
    "BACKENDS", "StateBackend", "ObjectBackend", "ColumnarBackend",
    "DeviceBackend", "register_backend", "ShardedDeviceBackend",
    "ShardedStateFleet",
    "CheckpointStore", "StageCheckpoint", "TopologyCheckpoint",
    "checkpoint_stage", "checkpoint_topology", "restore_stage",
    "restore_topology",
    "ChaosRunner", "DropDelivery", "DuplicateDelivery", "FaultPlan",
    "FaultInjector", "KillTask", "RecoveryEvent", "StallTask",
    "TaskKilled", "TaskStalled",
]


def __getattr__(name):
    # The device/sharded backends import jax at module scope; loading them
    # lazily keeps `import repro.streams` jax-free for ModHash/object-backend
    # users.
    if name in ("DeviceStateFleet", "DeviceTaskView"):
        from . import device
        return getattr(device, name)
    if name in ("ShardedDeviceBackend", "ShardedStateFleet"):
        from . import sharded
        return getattr(sharded, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

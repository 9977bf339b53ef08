#!/usr/bin/env python3
"""Smoke test of the keyed stream engine's device path on a TPU.

    python3 chip_smoke.py [--seed N]            # one chip, phases 1-5
    python3 chip_smoke.py --chips 4 [--seed N]  # four chips, sharded only

It drives the engine through the entry points a user calls (``KeyedStage``
via ``keyed_stage``, ``Topology``, ``ChaosRunner``) at a real state size:

* a key domain of 2^22 ids, the device backend's ``device_domain_max``;
* a window of 4 intervals, so the dense ring is (5, 2^22+1) int32 x 2
  planes, about 168 MB on the device;
* 15 tasks (the paper's Table II instance count) under the Mixed planner
  at theta_max=0.08 behind a Hash32 router;
* about 2M tuples per interval, Zipf(0.9) over the domain, with the hot set
  moving every few intervals (``hot_set_drift_trace``, made from
  ``--seed``), so the planner rebalances more than once.

One chip:

1. device check: JAX's first device must be a TPU;
2. single stage: WordCount on ``state_backend="device"`` with the compiled
   Pallas route against the same stream on the host columnar backend;
3. two-stage topology: a count per key, then a running max per bucket
   (NEXmark Q5's count-then-max shape, which runs the max-mode step),
   against columnar stages;
4. recovery: ``ChaosRunner`` with checkpoints every 2 intervals and one
   task killed mid-interval, against phase 2's fault-free device run;
5. kernels: compiled ``routing_lookup`` and ``key_stats`` at real size
   against the ``repro.kernels.ref`` oracles.

Four chips: phase 2's stream on ``state_backend="sharded", n_shards=4``
against columnar, and the ring must hold state on each of the 4 devices.

Every comparison is exact: interval reports, outputs, emitted sums, key
ownership and the state packs a checkpoint takes. Each phase prints its
wall seconds and how many programs it compiled; the last line is one JSON
object with ``"ok": true``. A failed check, a run without a TPU, or this
file outside its checkout exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.streams  # noqa: E402
from repro.core.balancer.hashing import Hash32  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.key_stats import key_stats  # noqa: E402
from repro.kernels.routing_lookup import routing_lookup  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.streams import (ChaosRunner, FaultPlan, KillTask,  # noqa: E402
                           MergeCounts, StageSpec, Topology, WordCount,
                           checkpoint_stage, hot_set_drift_trace, keyed_stage)

REPORT_FIELDS = ("interval", "tuples", "makespan", "migration_stall",
                 "throughput", "skewness", "theta", "migrated_bytes",
                 "table_size", "buffered")
TOPOLOGY_FIELDS = ("interval", "tuples_in", "stage_tuples", "critical_path",
                   "throughput", "migrated_bytes", "buffered")


@dataclasses.dataclass(frozen=True)
class Size:
    """One smoke configuration; ``Size()`` is the real one."""

    domain: int = 1 << 22          # key ids in [0, domain)
    tuples: int = 2_000_000        # per interval
    intervals: int = 8
    window: int = 4
    tasks: int = 15
    theta_max: float = 0.08
    zipf: float = 0.9
    hot: int = 256                 # ranks that move when the hot set shifts
    shift_every: int = 3           # intervals between hot-set moves
    buckets: int = 1024            # phase 3's second-stage key domain
    kill_interval: int = 5         # phase 4: kill a task mid-interval here
    stats_tuples: int = 262_144    # phase 5: key_stats stream length
    stats_keys: int = 65_536       # phase 5: key_stats key domain
    tables: tuple = (128, 2048)    # phase 5: routing table capacities


class SmokeFailure(Exception):
    """A result differed from its reference."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits included) via
    ``jax.monitoring``; register once per process."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# -- stages and comparisons ---------------------------------------------------

def make_stage(size: Size, operator, backend: str, seed: int, **kwargs):
    return keyed_stage(operator, size.tasks, size.theta_max,
                       window=size.window, seed=seed, hash_cls=Hash32,
                       state_backend=backend, **kwargs)


def device_stage(size: Size, operator, seed: int):
    """The one-chip device path: dense ring + the Pallas dense route."""
    stage = make_stage(size, operator, "device", seed, substrate="pallas")
    check(stage.state_backend == "device",
          f"stage resolved to {stage.state_backend!r}, not the device")
    check(stage._kernel_interpret == (not on_tpu()),
          "Pallas kernels run in interpret mode on the TPU")
    return stage


def bucket_of(keys, values, *, buckets: int):
    return keys % buckets


def same_reports(got, want, what: str) -> None:
    check(len(got) == len(want),
          f"{what}: {len(got)} reports vs {len(want)}")
    for g, w in zip(got, want):
        for field in REPORT_FIELDS:
            check(getattr(g, field) == getattr(w, field),
                  f"{what}: {field} differs at interval {w.interval}: "
                  f"{getattr(g, field)!r} vs {getattr(w, field)!r}")
        check(np.array_equal(np.asarray(g.task_loads),
                             np.asarray(w.task_loads)),
              f"{what}: task_loads differ at interval {w.interval}")


def same_packs(got, want, what: str) -> None:
    """Per-task state packs (keys, slot values, sizes, presence) equal."""
    check(len(got) == len(want), f"{what}: {len(got)} packs vs {len(want)}")
    for t, (g, w) in enumerate(zip(got, want)):
        check(np.array_equal(g.keys, w.keys),
              f"{what}: task {t} holds different keys")
        if not w.keys.size:
            continue
        for field in ("vals", "sizes", "present", "col_iv"):
            check(np.array_equal(getattr(g, field), getattr(w, field)),
                  f"{what}: task {t} state {field} differs")


@dataclasses.dataclass
class StageResult:
    """What a finished stage is compared by."""

    reports: list
    outputs: Dict[int, object]
    emitted_sum: float
    packs: list
    rebalances: List[int]
    locations: List[List[int]]     # key_location of each probe key


def stage_result(stage, probes: np.ndarray) -> StageResult:
    return StageResult(list(stage.reports), dict(stage.outputs),
                       stage.emitted_sum, checkpoint_stage(stage).packs,
                       stage.controller.triggered_intervals(),
                       [stage.key_location(int(k)) for k in probes])


def same_stage(got: StageResult, want: StageResult, what: str) -> None:
    same_reports(got.reports, want.reports, what)
    check(got.emitted_sum == want.emitted_sum,
          f"{what}: emitted_sum {got.emitted_sum!r} vs {want.emitted_sum!r}")
    check(got.outputs == want.outputs, f"{what}: outputs differ")
    check(got.locations == want.locations, f"{what}: key_location differs")
    same_packs(got.packs, want.packs, what)


def probe_keys(size: Size, trace: List[np.ndarray], seed: int) -> np.ndarray:
    """Keys whose ``key_location`` is compared: each interval's hottest keys
    plus a seeded sample of the domain."""
    hot = [np.unique(keys[:4096]) for keys in trace]
    rng = np.random.default_rng(seed + 7)
    return np.unique(np.concatenate(
        hot + [rng.integers(0, size.domain, 256)]))


def make_trace(size: Size, seed: int) -> List[np.ndarray]:
    return hot_set_drift_trace(size.domain, size.zipf, size.tuples,
                               size.intervals, hot=size.hot,
                               shift_every=size.shift_every, seed=seed)


# -- phases ---------------------------------------------------------------------

def phase_single_stage(size: Size, trace, seed: int):
    """Phase 2: WordCount, device backend + Pallas route vs columnar."""
    probes = probe_keys(size, trace, seed)
    dev = device_stage(size, WordCount(), seed)
    donated = []
    for keys in trace:
        before = dev.backend._fleet.vals
        dev.process_interval_arrays(keys)
        donated.append(before.is_deleted())
    fleet = dev.backend._fleet
    check(fleet.vals.shape == (size.window + 1, size.domain + 1),
          f"device ring is {fleet.vals.shape}, not the full domain")
    check(all(donated[1:]) == on_tpu(),
          f"state donation per interval: {donated}")
    got = stage_result(dev, probes)
    col = make_stage(size, WordCount(), "columnar", seed)
    for keys in trace:
        col.process_interval_arrays(keys)
    want = stage_result(col, probes)
    same_stage(got, want, "device vs columnar")
    check(len(got.rebalances) >= 2,
          f"the planner rebalanced at intervals {got.rebalances}; "
          "the smoke needs at least two")
    return got, f"rebalances at intervals {got.rebalances}"


def count_then_max(size: Size, seed: int, device: bool) -> Topology:
    def stage(op, s):
        if device:
            return device_stage(size, op, s)
        return make_stage(size, op, "columnar", s)
    return Topology([
        StageSpec("count", stage(WordCount(), seed)),
        StageSpec("max", stage(MergeCounts(), seed + 1),
                  rekey=functools.partial(bucket_of, buckets=size.buckets)),
    ])


def phase_topology(size: Size, trace, seed: int):
    """Phase 3: count per key -> running max per bucket, device vs columnar."""
    topos = [count_then_max(size, seed, device) for device in (True, False)]
    for topo in topos:
        for keys in trace:
            topo.process_interval(keys)
    dev, col = topos
    for g, w in zip(dev.reports, col.reports):
        for field in TOPOLOGY_FIELDS:
            check(getattr(g, field) == getattr(w, field),
                  f"topology: {field} differs at interval {w.interval}")
    check(np.array_equal(dev.last_emit_keys, col.last_emit_keys)
          and np.array_equal(dev.last_emit_values, col.last_emit_values),
          "topology: final emit streams differ")
    probes = {"count": probe_keys(size, trace, seed),
              "max": np.arange(size.buckets)}
    for name in dev.names:
        same_stage(stage_result(dev[name], probes[name]),
                   stage_result(col[name], probes[name]),
                   f"topology stage {name!r}")
    triggered = dev.rebalances_by_stage()
    return None, f"rebalances per stage {triggered}"


def phase_recovery(size: Size, trace, seed: int, fault_free: StageResult):
    """Phase 4: kill a task mid-interval under ChaosRunner on the device
    backend; the recovered run must equal the fault-free one."""
    stage = device_stage(size, WordCount(), seed)
    runner = ChaosRunner(stage, FaultPlan([KillTask(
        interval=size.kill_interval, task=size.tasks // 2, site="mid")]),
        checkpoint_every=2)
    donated_after_restore = None
    for keys in trace:
        recovered = bool(runner.events)
        before = stage.backend._fleet.vals
        runner.process_interval(keys)
        if recovered and donated_after_restore is None:
            donated_after_restore = before.is_deleted()
    check([(e.interval, e.kind) for e in runner.events]
          == [(size.kill_interval, "kill@mid")],
          f"recovery events {runner.events}")
    check(donated_after_restore == on_tpu(),
          "state donation on the fleet the recovery rebuilt")
    same_stage(stage_result(stage, np.zeros(0, np.int64)),
               dataclasses.replace(fault_free, locations=[]),
               "recovered vs fault-free")
    ev = runner.events[0]
    return None, (f"killed task {size.tasks // 2} at interval {ev.interval},"
                  f" replayed {ev.replayed} intervals")


def phase_kernels(size: Size, seed: int):
    """Phase 5: compiled kernels at real size vs the jnp oracles."""
    interpret = not on_tpu()
    rng = np.random.default_rng(seed + 5)
    d1 = size.domain + 1
    keys = jnp.arange(d1, dtype=jnp.int32)
    chunk = min(1 << 16, d1)
    n_chunks = -(-d1 // chunk)
    padded = jnp.pad(keys, (0, n_chunks * chunk - d1), constant_values=-1)
    host_hash = Hash32(size.tasks, seed=seed)(np.arange(d1, dtype=np.int64))
    for cap in size.tables:
        tk = np.full(cap, -1, np.int32)
        td = np.zeros(cap, np.int32)
        n_real = cap - cap // 4                  # leave empty slots
        tk[:n_real] = rng.choice(size.domain, n_real, replace=False)
        td[:n_real] = rng.integers(0, size.tasks, n_real)
        got = np.asarray(routing_lookup(keys, jnp.asarray(tk),
                                        jnp.asarray(td), size.tasks,
                                        seed=seed, interpret=interpret))
        oracle = jax.jit(lambda k, t, d: jax.lax.map(
            lambda c: ref.routing_lookup(c, t, d, size.tasks, seed=seed),
            k.reshape(n_chunks, chunk)).reshape(-1))
        want = np.asarray(oracle(padded, jnp.asarray(tk),
                                 jnp.asarray(td)))[:d1]
        check(np.array_equal(got, want),
              f"routing_lookup differs from ref with a {cap}-entry table")
        host = host_hash.copy()
        host[tk[:n_real]] = td[:n_real]
        check(np.array_equal(got, host),
              f"routing_lookup differs from host Hash32 with a {cap}-entry "
              "table")
    sk = rng.integers(0, size.stats_keys, size.stats_tuples).astype(np.int32)
    sk[rng.random(sk.size) < 0.01] = -1          # padding lanes
    costs = rng.integers(1, 5, sk.size).astype(np.float32)
    freq, cost = key_stats(jnp.asarray(sk), jnp.asarray(costs),
                           size.stats_keys, interpret=interpret)
    rfreq, rcost = jax.jit(ref.key_stats, static_argnums=2)(
        jnp.asarray(sk), jnp.asarray(costs), size.stats_keys)
    check(np.array_equal(np.asarray(freq), np.asarray(rfreq)),
          "key_stats frequencies differ from ref")
    check(np.array_equal(np.asarray(cost), np.asarray(rcost)),
          "key_stats costs differ from ref")
    return None, (f"routing over {d1} keys with tables {size.tables}, "
                  f"key_stats {size.stats_tuples} x {size.stats_keys}")


def phase_sharded(size: Size, trace, seed: int, n_shards: int):
    """Four chips: phase 2's stream on the sharded backend vs columnar."""
    probes = probe_keys(size, trace, seed)
    shd = make_stage(size, WordCount(), "sharded", seed, n_shards=n_shards)
    check(shd.state_backend == "sharded", "stage is not sharded")
    for keys in trace:
        shd.process_interval_arrays(keys)
    fleet = shd.backend._fleet
    devices = jax.devices()[:n_shards]
    for name in ("vals", "pres"):
        shards = getattr(fleet, name).addressable_shards
        check(sorted(s.device.id for s in shards)
              == sorted(d.id for d in devices),
              f"{name} shards sit on {[s.device for s in shards]}")
        check(all(s.data.size and np.asarray(s.data).any() for s in shards),
              f"{name}: a device holds an empty shard")
    got = stage_result(shd, probes)
    col = make_stage(size, WordCount(), "columnar", seed)
    for keys in trace:
        col.process_interval_arrays(keys)
    same_stage(got, stage_result(col, probes), "sharded vs columnar")
    block = fleet.vals.addressable_shards[0].data.shape
    return None, (f"{n_shards} shards of {block}, rebalances at intervals "
                  f"{got.rebalances}")


# -- driver ---------------------------------------------------------------------

def run_phase(counter: CompileCounter, label: str, fn, *args):
    c0, h0, t0 = counter.compiles, counter.cache_hits, time.perf_counter()
    out, detail = fn(*args)
    secs = time.perf_counter() - t0
    print(f"{label}: ok in {secs:.1f} s, {counter.compiles - c0} compiles "
          f"({counter.cache_hits - h0} from the persistent cache); {detail}",
          flush=True)
    return out


def run(size: Size, chips: int, seed: int,
        counter: Optional[CompileCounter] = None) -> dict:
    """Every phase for ``chips``; raises :class:`SmokeFailure` on a
    mismatch. Returns the device as JAX reports it."""
    counter = counter or CompileCounter()
    t0 = time.perf_counter()
    trace = make_trace(size, seed)
    print(f"traffic: {size.intervals} intervals x {size.tuples} tuples over "
          f"{size.domain} keys in {time.perf_counter() - t0:.1f} s",
          flush=True)
    if chips == 1:
        fault_free = run_phase(counter, "phase 2 single stage",
                               phase_single_stage, size, trace, seed)
        run_phase(counter, "phase 3 two-stage topology", phase_topology,
                  size, trace, seed)
        run_phase(counter, "phase 4 recovery", phase_recovery, size, trace,
                  seed, fault_free)
        run_phase(counter, "phase 5 kernels", phase_kernels, size, seed)
    else:
        run_phase(counter, f"sharded on {chips} chips", phase_sharded, size,
                  trace, seed, chips)
    dev = jax.devices()
    return {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases 1-5 on one chip; 4: the sharded backend "
                         "on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the traffic and the kernel inputs")
    args = ap.parse_args(argv)
    src = Path(repro.streams.__file__).resolve()
    if not src.is_relative_to(ROOT / "src"):
        print(f"repro is imported from {src}, not from this checkout",
              file=sys.stderr)
        return 1
    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"phase 1 device check: FAILED, JAX's first device is "
              f"{dev[0].platform!r}, not a TPU", file=sys.stderr)
        return 1
    if len(dev) < args.chips:
        print(f"phase 1 device check: FAILED, {len(dev)} devices for "
              f"--chips {args.chips}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"phase 1 device check: ok, {dev[0].device_kind} x {len(dev)}, "
          f"jax {jax.__version__}, compile cache {cache}", flush=True)
    try:
        device = run(Size(), args.chips, args.seed)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

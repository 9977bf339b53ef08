"""Interval-synchronous DSPE with the paper's rebalance protocol (Fig. 5).

One keyed stage = N_D task instances consuming a key-partitioned tuple
stream under the controller's mixed assignment function. Intervals are
discretized (paper Sec. II-A); each interval is processed in micro-batches so
the Pause -> migrate -> Resume protocol has real in-flight traffic to handle:

  * tuples whose key is in Delta(F, F') during the migration window are
    buffered ("cached locally" per the paper) and replayed on Resume;
  * tuples for all other keys flow uninterrupted (the paper's key property);
  * per-key state moves between task stores atomically at the boundary.

The engine also produces the performance model used by the benchmarks:
interval makespan = max per-task cost + migration stall, so throughput =
tuples / makespan (relative units; the paper measures the same shape of
quantity on Storm).

Router + controller shell over pluggable state backends
-------------------------------------------------------
:class:`KeyedStage` itself only owns what is backend-independent: routing
(``_dest_batch``, numpy or the Pallas kernel), the controller handoff and
report assembly (``_finish_interval``), the pause-window clock, elastic
scaling, and the per-tuple reference loop (``vectorized=False``) that serves
as the parity oracle. Everything state-shaped — store layout, interval
execution, migration, step-1 stats — lives behind the
:class:`~repro.streams.backends.StateBackend` protocol; see
:mod:`repro.streams.backends` for the object/columnar/device backends and
:mod:`repro.streams.sharded` for the multi-device mesh backend.
``tests/test_engine_parity.py`` proves the vectorized backends produce
:class:`IntervalReport` streams identical to the reference loop, and
``benchmarks/engine_fastpath.py`` measures the speedups.

Multi-stage topologies chain stages through
:meth:`KeyedStage.process_interval_emits`, which additionally returns the
operator's full emit stream as ``(keys, values)`` arrays in canonical
source-position order (see :mod:`repro.streams.topology` and the batched
emit contract in :mod:`repro.streams.operators`).

Substrate flag
--------------
``substrate="numpy"`` (default) computes routing and stats on host numpy.
``substrate="pallas"`` runs routing through the Pallas mixed-dispatch kernel
(:mod:`repro.kernels.routing_lookup`) and step-1 stats aggregation through
the fused histogram kernel (:mod:`repro.kernels.key_stats`), with the numpy
path as the reference semantics. Requirements: the assignment's hash router
must be :class:`repro.core.balancer.hashing.Hash32` (the device-canonical
fmix32 hash — ``ModHash`` uses splitmix64, which the kernels do not
implement) and key ids must fit int32. Stats come back float32, so reports
match numpy to ~1e-6 relative rather than bit-for-bit. See
``docs/architecture.md`` ("Kernels") for when to flip this flag.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.balancer import Assignment, KeyStats, metrics
from repro.core.controller import RebalanceController
from repro.core.telemetry import count, span

from .backends import SKETCH_PENDING, resolve_backend
from .operators import Operator

SUBSTRATES = ("numpy", "pallas")
STATE_BACKENDS = ("auto", "columnar", "object", "device", "sharded")


@dataclasses.dataclass
class IntervalReport:
    interval: int
    tuples: int
    makespan: float              # max task cost (critical path)
    migration_stall: float       # migration bytes / bandwidth
    throughput: float            # tuples / (makespan + stall)
    skewness: float              # max load / mean load
    theta: float
    migrated_bytes: float
    table_size: int
    plan_time_s: float
    buffered: int                # tuples held during Pause
    task_loads: np.ndarray


class KeyedStage:
    """N_D task instances + controller-owned assignment (one logical operator).

    Args:
      vectorized: use the array-at-a-time fast path (default). ``False``
        selects the per-tuple reference loop — same results, ~10x slower;
        kept for parity testing and as executable documentation.
      substrate: ``"numpy"`` or ``"pallas"`` — see the module docstring.
        ``state_backend="sharded"`` rejects ``"pallas"`` (its route does
        not run the kernel).
      state_backend: which :class:`~repro.streams.backends.StateBackend`
        holds the keyed state. ``"auto"`` (default) resolves device >
        columnar > object: the columnar store when the operator declares a
        ``columnar_spec`` and the stage is vectorized — state then lives in
        flat per-task arrays and each macro-batch is ONE whole-interval
        operator dispatch — promoted to ``"device"`` only when the operator
        also has device closed forms (``device_mode``), the router is Hash32
        AND jax runs on an accelerator backend (on CPU the columnar store
        wins, so auto behavior there is unchanged). ``"object"`` forces the
        dict-of-KeyState store (the compatibility/parity backend, and the
        only one custom per-tuple operators can use); ``"columnar"`` forces
        the array store; ``"device"`` keeps state as device-resident arrays
        and fuses the whole interval into one jitted step (see
        :mod:`repro.streams.device`); ``"sharded"`` shards that same dense
        ring across a JAX mesh of ``n_shards`` devices (explicit-only; see
        :mod:`repro.streams.sharded`). Forced backends raise ``ValueError``
        when the operator/router cannot support them.
      n_shards: device count for ``state_backend="sharded"`` (default: every
        local jax device). Ignored by the other backends.
      device_domain_max: the device/sharded backends allocate dense state per
        key id; ids at or above this bound raise instead of silently
        exploding memory (sparse huge domains belong on the columnar
        backend).
      kernel_interpret: Pallas ``interpret=`` mode for the routing/stats
        kernels. ``None`` (default) auto-selects: compiled on real TPU
        backends, interpret elsewhere (CPU has no Mosaic lowering).
      stats_dense_max: in the pallas substrate, the stats histogram kernel
        needs a dense key domain; domains larger than this fall back to the
        numpy segment-sum for step 1 (routing stays on the kernel).
    """

    def __init__(self, operator: Operator, controller: RebalanceController,
                 window: int = 1, migration_bandwidth: float = 1e6,
                 micro_batches: int = 8, migration_batches: int = 2,
                 vectorized: bool = True, substrate: str = "numpy",
                 state_backend: str = "auto",
                 n_shards: Optional[int] = None,
                 kernel_interpret: Optional[bool] = None,
                 stats_dense_max: int = 1 << 20,
                 device_domain_max: int = 1 << 22,
                 algorithm=None):
        if substrate not in SUBSTRATES:
            raise ValueError(f"unknown substrate {substrate!r}; "
                             f"choose from {SUBSTRATES}")
        self.operator = operator
        self.controller = controller
        if algorithm is not None:
            # same spec grammar as RebalanceController(algorithm=) — name,
            # planner callable, or configured PartitionStrategy instance
            # (see RebalanceController.use_algorithm); installed before
            # backend resolution so backend support checks see the strategy.
            controller.use_algorithm(algorithm)
        if (controller.strategy.needs_merge_stage
                and not getattr(operator, "split_safe", False)):
            raise ValueError(
                f"algorithm {controller.algorithm_name!r} splits keys across "
                f"tasks but operator {operator.name!r} is not split-safe; "
                "use a split-safe operator (e.g. PartialWordCount) with a "
                "downstream merge stage (repro.streams.topology), or a "
                "table-planner algorithm")
        self.window = window
        self.n_tasks = controller.assignment.n_dest
        self.n_shards = n_shards
        self.device_domain_max = device_domain_max
        self.migration_bandwidth = migration_bandwidth
        self.micro_batches = micro_batches
        self.migration_batches = migration_batches
        self.vectorized = vectorized
        self.substrate = substrate
        self.stats_dense_max = stats_dense_max
        self.reports: List[IntervalReport] = []
        self.outputs: Dict[int, Any] = {}
        self.emitted_sum = 0.0                      # running sum of numeric emits
        self.last_stats: Optional[KeyStats] = None
        self._interval = 0
        self._pending_delta: Optional[set] = None   # keys paused this interval
        self._pending_delta_arr: Optional[np.ndarray] = None
        self._migrated_bytes_pending = 0.0
        self._plan_time_pending = 0.0
        self._table_capacity = 0      # pallas routing-table pad, high-water mark
        self._route_cache = None      # (cache key, device tk, device td)
        #: failure-injection seam (repro.streams.faults): when set, called as
        #: ``failpoint(site, stage)`` at the engine's crash points — "deliver"
        #: (before any mutation) and "mid" (state mutated, no report yet).
        #: None (the default) is zero-overhead for production runs.
        self.failpoint = None
        self._kernel_interpret = kernel_interpret
        # backend selection (and its support errors) precedes substrate init
        backend_cls = resolve_backend(state_backend, operator, controller,
                                      vectorized, substrate)
        if substrate == "pallas":
            self._init_pallas(kernel_interpret)
        self.backend = backend_cls(self)
        self.state_backend = self.backend.name
        self.stores = [self.backend.new_store() for _ in range(self.n_tasks)]
        # wire the migration executor (paper steps 5-6)
        self.controller.executor = self._execute_migration

    def _init_pallas(self, kernel_interpret: Optional[bool]) -> None:
        from repro.core.balancer.hashing import Hash32
        router = self.controller.assignment.hash_router
        if not isinstance(router, Hash32):
            raise ValueError(
                "substrate='pallas' requires a Hash32 router (device-"
                f"canonical fmix32); got {type(router).__name__}. ModHash's "
                "splitmix64 has no 32-bit kernel equivalent.")
        import jax                                    # lazy: numpy path stays jax-free
        from repro.kernels.key_stats import key_stats
        from repro.kernels.routing_lookup import routing_lookup
        self._kernel_route = routing_lookup
        self._kernel_stats = key_stats
        self._hash_seed = router.seed
        if kernel_interpret is None:
            # compiled kernels on real TPU backends; interpret elsewhere
            kernel_interpret = jax.default_backend() != "tpu"
        self._kernel_interpret = bool(kernel_interpret)

    # -- failure-injection seam (repro.streams.faults) --------------------------
    def _failpoint(self, site: str) -> None:
        if self.failpoint is not None:
            self.failpoint(site, self)

    # -- pause-window clock (protocol steps 4/7) --------------------------------
    def begin_interval(self) -> int:
        self._interval += 1
        return self._interval

    def pause_window(self, n: int) -> Optional[int]:
        """Index bound of the pause window, or None when no migration is in
        flight: the first ``migration_batches`` of ``micro_batches`` slices
        buffer Delta-keys while migration completes."""
        if not n or self._pending_delta_arr is None:
            return None
        edges = np.linspace(0, n, self.micro_batches + 1).astype(int)
        return int(edges[min(self.migration_batches, self.micro_batches)])

    def clear_pause(self) -> None:
        self._pending_delta = None
        self._pending_delta_arr = None

    # -- migration executor (paper steps 5-6) -----------------------------------
    def _execute_migration(self, moved_keys: np.ndarray, old: Assignment,
                           new: Assignment) -> None:
        """Controller-invoked: the backend moves the state, the stage books
        the stall and opens the pause window for Delta(F, F')."""
        keys = np.asarray(moved_keys, dtype=np.int64)
        with span("migrate"):
            moved_bytes = self.backend.migrate(keys, old, new)
        self._migrated_bytes_pending += moved_bytes
        count("migrated_keys", keys.size)
        # the reference loop materializes the membership set lazily; the
        # vectorized backends only ever consult the array (np.isin)
        self._pending_delta = None
        self._pending_delta_arr = keys

    # -- one interval of traffic ------------------------------------------------
    def process_interval(self, tuples: Sequence[Tuple[int, Any]]) -> IntervalReport:
        """Process one interval given ``(key, value)`` tuples (list API)."""
        keys = np.fromiter((k for k, _ in tuples), dtype=np.int64,
                           count=len(tuples))
        values = [v for _, v in tuples]
        return self.process_interval_arrays(keys, values)

    def process_interval_arrays(self, keys: np.ndarray,
                                values: Optional[Sequence[Any]] = None
                                ) -> IntervalReport:
        """Array-native entry point: ``keys`` as int64 array, ``values`` as an
        aligned sequence (or None when the operator sets ``needs_values``
        False). This is the zero-conversion path used by the benchmarks."""
        self._failpoint("deliver")
        if not self.vectorized:
            return self._process_interval_reference(keys, values)
        return self.backend.process_interval(keys, values)

    def process_interval_emits(self, keys: np.ndarray,
                               values: Optional[Sequence[Any]] = None
                               ) -> Tuple[IntervalReport, np.ndarray,
                                          np.ndarray]:
        """Process one interval and also return the operator's emit stream.

        Returns ``(report, emit_keys, emit_values)``. Emits are ordered by
        source-tuple position (the fan-out emits of one tuple stay adjacent,
        in emit order) — per-key state only depends on that key's own tuple
        order, which pause/replay preserves, so ALL engine paths produce
        this exact stream. That canonical order is what makes chained stages
        parity-testable; it is the stage-to-stage hand-off used by
        :class:`repro.streams.topology.Topology`.
        """
        self._failpoint("deliver")
        if not self.vectorized:
            return self._process_interval_reference(keys, values,
                                                    collect_emits=True)
        return self.backend.process_interval(keys, values, collect_emits=True)

    def _dest_batch(self, keys: np.ndarray) -> np.ndarray:
        """Destinations for a key batch — the strategy's per-tuple router when
        one is installed, else F(k) via numpy Assignment.dest or the Pallas
        kernel. Called exactly ONCE per interval batch on every engine path
        (routers are stateful: their load estimates advance per call)."""
        strategy = self.controller.strategy
        if strategy.is_router:
            return strategy.route(keys)
        if self.substrate == "pallas" and keys.size:
            if int(keys.max()) > np.iinfo(np.int32).max or int(keys.min()) < 0:
                raise ValueError(
                    "substrate='pallas' requires key ids in [0, 2^31): the "
                    "routing kernel operates on int32 and larger ids would "
                    "silently alias")
            assignment = self.controller.assignment
            # pad the table to a stable capacity (next power of two, >= 128):
            # routing_lookup is jitted on the table shape, so size-exact
            # padding would retrace on every rebalance that resizes the table.
            # The capacity is a per-stage high-water mark — recomputing it
            # from the current table_size would shrink it again when the
            # table shrinks, so a table oscillating across a power-of-two
            # boundary (e.g. 128<->129 under Mixed churn) would retrace the
            # kernel every interval.
            needed = max(128, 1 << max(0, assignment.table_size - 1).bit_length())
            if needed > self._table_capacity:
                self._table_capacity = needed
            # Device-side table cache: rebuilding table_arrays and re-running
            # jnp.asarray uploads every interval is pure waste when the
            # assignment didn't change. The controller bumps
            # assignment_version on every rebalance/rescale, so (version,
            # table_size, capacity) only moves when the table can differ.
            # (In-place table mutation without a size change bypasses the
            # controller and is not supported by this cache.)
            cache_key = (self.controller.assignment_version,
                         assignment.table_size, self._table_capacity)
            from .device import to_device, to_host  # lazy: jax-only
            if self._route_cache is None or self._route_cache[0] != cache_key:
                tk, td = assignment.table_arrays(self._table_capacity)
                self._route_cache = (cache_key,
                                     to_device(tk.astype(np.int32)),
                                     to_device(td.astype(np.int32)))
            _, tk_dev, td_dev = self._route_cache
            out = self._kernel_route(
                to_device(keys.astype(np.int32)),
                tk_dev, td_dev,
                assignment.n_dest, seed=self._hash_seed,
                interpret=self._kernel_interpret)
            return to_host(out).astype(np.int64)
        return self.controller.assignment.dest(keys)

    def _finish_interval(self, iv: int, n: int, task_cost: np.ndarray,
                         buffered_count: int,
                         stats: Optional[KeyStats]) -> IntervalReport:
        # -- measurement + controller handoff (paper steps 1-2) -----------------
        stall = self._migrated_bytes_pending / self.migration_bandwidth
        makespan = float(task_cost.max()) if n else 0.0
        report = IntervalReport(
            interval=iv, tuples=n, makespan=makespan, migration_stall=stall,
            throughput=n / (makespan + stall) if (makespan + stall) > 0 else 0.0,
            skewness=metrics.skewness(task_cost) if n else 1.0,
            theta=metrics.theta(task_cost) if n else 0.0,
            migrated_bytes=self._migrated_bytes_pending,
            table_size=self.controller.assignment.table_size,
            plan_time_s=self._plan_time_pending,
            buffered=buffered_count, task_loads=task_cost,
        )
        self.reports.append(report)
        self._migrated_bytes_pending = 0.0
        self._plan_time_pending = 0.0
        if stats is not None:
            # pin the event to the STAGE interval: a stats-free interval
            # (no tuples, no held state) skips the controller, and its
            # private counter would silently lag the stage clock otherwise
            if stats is SKETCH_PENDING:
                # the backend streamed aggregates into the controller's
                # sketch; close the round on the head-only snapshot
                ev = self.controller.on_interval(None, interval=iv)
                self.last_stats = self.controller.last_stats
            else:
                self.last_stats = stats
                ev = self.controller.on_interval(stats, interval=iv)
            if ev.result is not None:
                self._plan_time_pending = ev.result.plan_time_s
        return report

    # -- reference per-tuple path (parity oracle; vectorized=False) ------------
    def _process_interval_reference(self, keys: np.ndarray,
                                    values: Optional[Sequence[Any]],
                                    collect_emits: bool = False):
        iv = self.begin_interval()
        n = int(keys.shape[0])
        vals = values if values is not None else [None] * n
        if self._pending_delta is None and self._pending_delta_arr is not None:
            self._pending_delta = set(self._pending_delta_arr.tolist())
        task_cost = np.zeros(self.n_tasks)
        key_cost: Dict[int, float] = defaultdict(float)
        key_freq: Dict[int, float] = defaultdict(float)
        buffer: List[Tuple[int, int, Any]] = []      # (position, key, value)
        buffered_count = 0
        emit_log: Optional[List[Tuple[int, int, Any]]] = \
            [] if collect_emits else None

        dests = self._dest_batch(keys) if n else np.zeros(0, np.int64)

        batch_edges = np.linspace(0, n, self.micro_batches + 1).astype(int)
        for b in range(self.micro_batches):
            lo, hi = batch_edges[b], batch_edges[b + 1]
            migrating = (self._pending_delta is not None
                         and b < self.migration_batches)
            if not migrating and buffer:
                # Resume: replay buffered tuples with the CURRENT assignment
                for pos, k, v in buffer:
                    d = int(self.controller.assignment.dest(
                        np.asarray([k], dtype=np.int64))[0])
                    self._run_one(d, iv, k, v, pos, task_cost, key_cost,
                                  key_freq, emit_log)
                buffer.clear()
                self.clear_pause()
            for i in range(lo, hi):
                k, v = int(keys[i]), vals[i]
                if migrating and k in self._pending_delta:
                    buffer.append((i, k, v))        # Pause: cache locally
                    buffered_count += 1
                    continue
                self._run_one(int(dests[i]), iv, k, v, i, task_cost, key_cost,
                              key_freq, emit_log)
        if buffer:                                   # traffic ended mid-pause
            for pos, k, v in buffer:
                d = int(self.controller.assignment.dest(
                    np.asarray([k], dtype=np.int64))[0])
                self._run_one(d, iv, k, v, pos, task_cost, key_cost, key_freq,
                              emit_log)
            buffer.clear()
        self.clear_pause()
        self._failpoint("mid")

        for store in self.stores:
            store.end_interval(iv)

        stats = self._collect_stats(key_cost, key_freq)
        report = self._finish_interval(iv, n, task_cost, buffered_count, stats)
        if not collect_emits:
            return report
        # canonical order = source position (replays keep their original
        # position, and a tuple's emits were appended contiguously)
        emit_log.sort(key=lambda t: t[0])
        ekeys = np.asarray([k for _, k, _ in emit_log], dtype=np.int64)
        evals = np.asarray([v for _, _, v in emit_log])
        return report, ekeys, evals

    def _run_one(self, d: int, interval: int, key: int, value: Any, pos: int,
                 task_cost, key_cost, key_freq, emit_log=None) -> None:
        outs, cost = self.operator.process(self.stores[d], interval, key, value)
        task_cost[d] += cost
        key_cost[key] += cost
        key_freq[key] += 1
        for ok, ov in outs:
            self.outputs[ok] = ov
            if isinstance(ov, (int, float)):
                self.emitted_sum += float(ov)
            if emit_log is not None:
                emit_log.append((pos, ok, ov))

    def _collect_stats(self, key_cost, key_freq) -> Optional[KeyStats]:
        # Paper step 1: every instance reports c(k) AND S(k,w) for each key
        # *assigned to it* — the stat universe is (keys seen this interval)
        # UNION (keys still holding window state). Omitting quiet stateful
        # keys would let a table cleanup strand their state on the old task.
        sizes: Dict[int, float] = {}
        for store in self.stores:
            sizes.update(store.sizes())
        universe = set(key_cost) | set(sizes)
        if not universe:
            return None
        keys = np.fromiter(sorted(universe), dtype=np.int64, count=len(universe))
        cost = np.fromiter((key_cost.get(int(k), 0.0) for k in keys),
                           dtype=np.float64)
        freq = np.fromiter((key_freq.get(int(k), 0.0) for k in keys),
                           dtype=np.float64)
        mem = np.fromiter((sizes.get(int(k), 0.0) for k in keys),
                          dtype=np.float64)
        if self.controller.stats_mode == "sketch":
            # the reference loop is dict-based (it materializes the exact
            # universe anyway), but in sketch mode it still hands off
            # through the sketch so the controller plans on the same
            # head-only contract as the vectorized backends
            self.controller.ingest(keys, cost, mem=mem, freq=freq)
            return SKETCH_PENDING
        return KeyStats(keys=keys, cost=cost, mem=mem, freq=freq)

    # -- elastic scaling (paper Fig. 15) ----------------------------------------
    def scale_to(self, n_tasks: int) -> None:
        """Add/remove task instances and rebalance state onto the new fleet.

        New stores must exist before the controller's migration executor runs;
        shrink requires draining removed stores first (state migrates away via
        the rescale plan, since no key may map to a dead task)."""
        if n_tasks < 1:
            raise ValueError(
                f"scale_to requires n_tasks >= 1, got {n_tasks}: a stage "
                "cannot run with an empty fleet")
        if self.controller.strategy.is_router:
            # fail before touching stores: controller.rescale would raise
            # anyway, but only after we had already grown the fleet
            self.controller.rescale(n_tasks, self.last_stats)
        if self.last_stats is None:
            raise RuntimeError("scale_to requires at least one processed interval")
        while len(self.stores) < n_tasks:
            self.stores.append(self.backend.new_store())
        self.controller.rescale(n_tasks, self.last_stats)
        # reconciliation sweep: the rescale executor only covers keys present
        # in the last interval's stats; stale-state keys re-hash too. Pack
        # extraction + mask splits keep this array-native on every backend.
        for s_idx, store in enumerate(self.stores):
            held, _ = store.sizes_arrays()
            if not held.size:
                continue
            dst = self.controller.assignment.dest(held)
            movers = held[dst != s_idx]
            if movers.size:
                pack = store.extract_batch(movers)
                self._migrated_bytes_pending += pack.nbytes
                pdst = self.controller.assignment.dest(pack.keys)
                for d in np.unique(pdst):
                    self.stores[int(d)].install_batch(pack.take(pdst == d))
        self.stores = self.stores[:n_tasks]
        self.n_tasks = n_tasks

    # -- invariant helpers for tests -------------------------------------------
    def total_state_keys(self) -> int:
        return sum(len(s.keys) for s in self.stores)

    def key_location(self, key: int) -> List[int]:
        return [i for i, s in enumerate(self.stores) if key in s.keys]

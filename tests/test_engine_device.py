"""Device state backend == dict state backend, observationally.

``state_backend='device'`` keeps all windowed state as device-resident
``jax.Array``s and fuses routing lookup, operator dispatch, window-ring
update and per-task cost bincount into one jitted step. That is a pure
representation change: under the same streams, rebalances, window>1
eviction and mid-run rescales it must produce the bit-identical
:class:`IntervalReport` stream, the same post-migration ``key_location``
map, and the same outputs/emit streams as the object-store oracle — the
Hypothesis property below drives randomized workloads through both
backends in lockstep, mirroring ``tests/test_state_columnar.py``.

All compared quantities are exact dyadics (integer counts; the self-join
pinned to ``probe_cost=1/64``), so comparisons are strict equality.

The retrace test pins the other half of the contract: the fused step
compiles ONCE and is reused across intervals (rebalances included,
because the dense dest table is data, not shape), and recompiles at most
once per ``scale_to`` (``n_tasks`` is a static argument).
"""

import numpy as np
import pytest

from repro.core import Assignment, BalanceConfig, ModHash, RebalanceController
from repro.core.balancer.hashing import Hash32
from repro.streams import (KeyedStage, MergeCounts, Operator, PartialWordCount,
                           WindowedSelfJoin, WordCount, WorkloadGen)

REPORT_FIELDS = ("interval", "tuples", "makespan", "migration_stall",
                 "throughput", "skewness", "theta", "migrated_bytes",
                 "table_size", "buffered")


def make_stage(op, backend, n_tasks=5, window=3, theta_max=0.05,
               table_max=300, seed=1, **kwargs):
    controller = RebalanceController(
        Assignment(Hash32(n_tasks, seed=seed)),
        BalanceConfig(theta_max=theta_max, table_max=table_max,
                      window=window),
        algorithm="mixed")
    return KeyedStage(op, controller, window=window, vectorized=True,
                      state_backend=backend, **kwargs)


def assert_stages_identical(dev, obj):
    assert len(dev.reports) == len(obj.reports)
    for rc, ro in zip(dev.reports, obj.reports):
        for field in REPORT_FIELDS:
            assert getattr(rc, field) == getattr(ro, field), field
        np.testing.assert_array_equal(rc.task_loads, ro.task_loads)
    assert dev.outputs == obj.outputs
    assert dev.emitted_sum == obj.emitted_sum
    assert dev.total_state_keys() == obj.total_state_keys()
    # identical post-migration ownership: every held key lives on the same
    # task under both backends (and exactly one task each)
    all_keys = set()
    for store in obj.stores:
        all_keys.update(store.keys)
    for k in all_keys:
        loc_d, loc_o = dev.key_location(k), obj.key_location(k)
        assert loc_d == loc_o, k
        assert len(loc_o) == 1, k


# -- the property: randomized workloads, rebalances, eviction, rescale --------

def _check_property(seed, z, f, window, theta, op_kind, scale_step):
    """Identical IntervalReport streams, emit streams and post-migration
    key_location maps over randomized skewed/fluctuating workloads with
    rebalances, window>1 eviction, and scale_to mid-run."""
    def op():
        return (WordCount() if op_kind == "wordcount"
                else WindowedSelfJoin(probe_cost=1.0 / 64))

    gens = [WorkloadGen(k=400, z=z, f=f, seed=seed, window=window)
            for _ in range(2)]
    stages = [make_stage(op(), b, window=window, theta_max=theta,
                         table_max=250, seed=seed % 13)
              for b in ("device", "object")]
    for i in range(5):
        keys = emits = None
        for gen, stage in zip(gens, stages):
            if i:
                gen.interval(stage.controller.assignment)
            drawn = gen.draw_tuples(1000).astype(np.int64)
            if keys is None:
                keys = drawn
            else:
                assert np.array_equal(drawn, keys), "streams diverged"
            _, ek, ev = stage.process_interval_emits(drawn,
                                                     np.full(1000, i))
            if emits is None:
                emits = (ek, ev)
            else:
                np.testing.assert_array_equal(ek, emits[0])
                np.testing.assert_array_equal(ev, emits[1])
        if scale_step is not None and i == 2:
            for stage in stages:
                stage.scale_to(scale_step)
            assert stages[0]._migrated_bytes_pending == \
                stages[1]._migrated_bytes_pending
    assert_stages_identical(*stages)


@pytest.mark.parametrize("seed,z,f,window,theta,op_kind,scale_step", [
    (2, 1.1, 0.8, 3, 0.0, "wordcount", None),
    (11, 0.9, 1.0, 4, 0.03, "selfjoin", 7),
    (23, 1.2, 0.3, 2, 0.0, "wordcount", 3),
], ids=["wordcount_rebalance", "selfjoin_scale_out", "wordcount_scale_in"])
def test_device_equals_object_store_fixed(seed, z, f, window, theta,
                                          op_kind, scale_step):
    """Deterministic instances of the property — run even without the
    optional hypothesis extra (bare envs, see ci.yml's bare-collect job)."""
    _check_property(seed, z, f, window, theta, op_kind, scale_step)


try:                                    # optional [test] extra
    from hypothesis import given, settings, strategies as st
except ImportError:                     # pragma: no cover - bare env
    pass
else:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           z=st.floats(0.6, 1.3),
           f=st.floats(0.0, 1.2),
           window=st.integers(2, 4),
           theta=st.sampled_from([0.0, 0.03, 0.2]),
           op_kind=st.sampled_from(["wordcount", "selfjoin"]),
           scale_step=st.sampled_from([None, 3, 7]))
    def test_device_equals_object_store_property(seed, z, f, window, theta,
                                                 op_kind, scale_step):
        _check_property(seed, z, f, window, theta, op_kind, scale_step)


def test_partial_wordcount_device_matches_object():
    gens = [WorkloadGen(k=350, z=1.0, f=0.6, seed=17, window=2)
            for _ in range(2)]
    stages = [make_stage(PartialWordCount(), b, window=2)
              for b in ("device", "object")]
    for i in range(4):
        for gen, stage in zip(gens, stages):
            if i:
                gen.interval(stage.controller.assignment)
            drawn = gen.draw_tuples(900).astype(np.int64)
            stage.process_interval_arrays(drawn, np.full(900, i))
    assert_stages_identical(*stages)


def test_merge_counts_device_matches_object():
    """max-mode folding (MergeCounts) through the device scatter-max path."""
    rng = np.random.default_rng(3)
    stages = [make_stage(MergeCounts(), b, window=2)
              for b in ("device", "object")]
    for _ in range(4):
        keys = rng.integers(0, 150, size=1200).astype(np.int64)
        vals = rng.integers(1, 40, size=1200)
        for stage in stages:
            stage.process_interval_arrays(keys, vals)
    assert_stages_identical(*stages)


def test_device_with_pallas_substrate_matches_object():
    """Device state + Pallas routing kernel (interpret mode on CPU): the
    dense dest table goes through ``routing_lookup`` instead of the jnp
    twin, and parity must still be exact."""
    gens = [WorkloadGen(k=300, z=1.0, f=0.5, seed=5, window=3)
            for _ in range(2)]
    stages = [make_stage(WordCount(), "device", substrate="pallas"),
              make_stage(WordCount(), "object")]
    for i in range(4):
        for gen, stage in zip(gens, stages):
            if i:
                gen.interval(stage.controller.assignment)
            drawn = gen.draw_tuples(500).astype(np.int64)
            stage.process_interval_arrays(drawn, np.full(500, i))
    assert_stages_identical(*stages)


# -- compile-once: the fused step must not retrace across intervals ----------

def test_no_retrace():
    """The fused step traces once and is reused for every subsequent
    interval — rebalances move keys by relabeling the host task mirror and
    swapping the (data, not shape) dense dest table, so they must not
    retrace. ``scale_to`` bumps ``n_dest`` (static in the dense route), so
    the route recompiles exactly once; the add-mode step is allowed no
    retrace at all."""
    from repro.core.telemetry import COUNTERS

    # jit caches are module-level, so this test must not share a trace
    # signature with any other test in the process: window=5 (unique ring
    # width), n_tasks=6 -> 9 (unique static args) and hash seed 99 (static
    # in the dense-route trace) make the counts order-independent.
    step = "retrace.device.interval_step"
    route = "retrace.device.route_dense"
    base = {k: COUNTERS.get(k, 0) for k in (step, route)}
    stage = make_stage(WordCount(), "device", n_tasks=6, window=5,
                       theta_max=0.03, seed=99)
    gen = WorkloadGen(k=400, z=1.1, f=0.8, seed=3, window=5)
    for i in range(6):
        if i:
            gen.interval(stage.controller.assignment)
        stage.process_interval_arrays(gen.draw_tuples(1000).astype(np.int64),
                                      np.full(1000, i))
    # at least one rebalance actually happened, so the no-retrace claim is
    # exercised against a moving assignment, not a static one
    assert stage.controller.assignment.table_size > 0
    d6 = {k: COUNTERS.get(k, 0) - base[k] for k in base}
    assert d6[step] == 1, d6
    assert d6[route] == 1, d6

    stage.scale_to(9)
    for i in range(6, 10):
        gen.interval(stage.controller.assignment)
        stage.process_interval_arrays(gen.draw_tuples(1000).astype(np.int64),
                                      np.full(1000, i))
    d10 = {k: COUNTERS.get(k, 0) - base[k] for k in base}
    # rescale: the add-mode step is shape-only (the task count lives in the
    # dense dest table and host mirrors), so it must NOT retrace; the dense
    # route is static in n_dest, so it retraces exactly once
    assert d10[step] == 1, d10
    assert d10[route] == 2, d10


@pytest.mark.parametrize("op_kind", ["add", "max"])
def test_stacked_steps_trace_once_per_domain(op_kind):
    """Each fused step (and the tuple-free eviction step) returns its per-key
    observables as ONE stacked array: it traces once per dense domain, and
    neither rebalances nor idle intervals retrace it."""
    from repro.core.telemetry import COUNTERS

    # window 6 (add) and 7 (max) are ring widths no other test uses, so the
    # module-level jit caches start cold for these shapes
    window = 6 if op_kind == "add" else 7
    op = WordCount() if op_kind == "add" else MergeCounts()
    names = ("retrace.device.interval_step", "retrace.device.evict_step")
    base = {k: COUNTERS.get(k, 0) for k in names}

    def traces():
        return tuple(COUNTERS.get(k, 0) - base[k] for k in names)

    stage = make_stage(op, "device", window=window, theta_max=0.03, seed=5)
    rng = np.random.default_rng(7)

    def run(hi, n=600):
        keys = (rng.zipf(1.3, size=n) % hi).astype(np.int64)
        stage.process_interval_arrays(keys, rng.integers(1, 50, size=n))

    for _ in range(4):
        run(400)                                 # domain 512
    assert stage.controller.assignment_version > 0   # it did rebalance
    assert traces() == (1, 0)
    for _ in range(window):                      # idle: the ring empties
        stage.process_interval_arrays(np.zeros(0, np.int64), np.zeros(0))
    assert stage.total_state_keys() == 0
    assert traces() == (1, 1)
    run(400)
    run(1000)                                    # domain grows to 1024
    run(1000)
    assert stage.backend._fleet.domain == 1024
    assert traces() == (2, 1)


def _record_device_spans(monkeypatch):
    """Every span ``repro.streams.device`` opens, as (name, stats)."""
    from repro.streams import device as dev_mod

    opened = []
    real = dev_mod.span

    def span(name, **args):
        opened.append((name, args))
        return real(name, **args)
    monkeypatch.setattr(dev_mod, "span", span)
    return opened


def _refresh_stage(op):
    """A stage whose plans refresh the dense route on some intervals and
    hit its cache on others (theta_max 0.15 over a settling Zipf stream)."""
    return (make_stage(op, "device", theta_max=0.15),
            WorkloadGen(k=400, z=1.1, f=0.8, seed=3, window=3))


@pytest.mark.parametrize("op_kind,rows", [("add", 4), ("max", 5)])
def test_one_step_copy_per_interval(op_kind, rows, monkeypatch):
    """An interval with tuples pulls the step's observables in ONE copy of
    ``rows`` (D+1,) int32 planes (add mode keeps its histogram on the host),
    plus one copy of the dense route table when the route refreshed and
    none on a cache hit; ``d2h_copies`` and ``d2h_bytes`` count exactly
    those copies."""
    from repro.core.telemetry import COUNTERS

    opened = _record_device_spans(monkeypatch)
    stage, gen = _refresh_stage(WordCount() if op_kind == "add"
                                else MergeCounts())
    watched = ("route_refreshes", "d2h_copies", "d2h_bytes")
    first = {k: COUNTERS.get(k, 0) for k in watched}
    rng = np.random.default_rng(11)
    refreshes = []
    for i in range(8):
        if i in (1, 2, 3):
            gen.interval(stage.controller.assignment)
        keys = gen.draw_tuples(5000).astype(np.int64)
        before = {k: COUNTERS.get(k, 0) for k in watched}
        opened.clear()
        stage.process_interval_arrays(keys, rng.integers(1, 50, size=5000))
        delta = {k: COUNTERS.get(k, 0) - before[k] for k in watched}
        width = (stage.backend._fleet.domain + 1) * 4
        pulls = [args["bytes"] for name, args in opened if name == "pull"]
        assert pulls.count(rows * width) == 1, pulls
        assert pulls.count(width) == delta["route_refreshes"], pulls
        assert len(pulls) == 1 + delta["route_refreshes"], pulls
        assert delta["d2h_copies"] == 1 + delta["route_refreshes"]
        assert delta["d2h_bytes"] == width * (rows + delta["route_refreshes"])
        refreshes.append(delta["route_refreshes"])
    assert set(refreshes) == {0, 1}, refreshes   # refreshes and cache hits
    total = {k: COUNTERS.get(k, 0) - first[k] for k in watched}
    assert total["d2h_bytes"] == width * (rows * 8 + sum(refreshes))


def _route_dense_reference(stage) -> np.ndarray:
    """``device._route_dense`` over the fleet's whole domain for the
    stage's current assignment, on the host."""
    import jax.numpy as jnp

    from repro.streams import device as dev_mod

    assignment = stage.controller.assignment
    tk, td = assignment.table_arrays()
    d1 = stage.backend._fleet.domain + 1
    return np.asarray(dev_mod._route_dense(
        jnp.arange(d1, dtype=jnp.int32), jnp.asarray(tk.astype(np.int32)),
        jnp.asarray(td.astype(np.int32)), n_dest=assignment.n_dest,
        seed=stage.backend._device_seed))


def test_deferred_dest_copy_equals_the_dense_route():
    """A refresh starts the dest table's host copy and caches no host array;
    the first read materializes it, element for element ``_route_dense``'s
    output for the assignment it was refreshed under, and a cache hit reuses
    that same host array."""
    stage, gen = _refresh_stage(WordCount())
    backend = stage.backend
    hits = refreshed = 0
    host_prev = None
    for i in range(8):
        if i in (1, 2, 3):
            gen.interval(stage.controller.assignment)
        keys = gen.draw_tuples(5000).astype(np.int64)
        stage.backend._fleet.ensure_domain(int(keys.max()) + 1)
        expected = _route_dense_reference(stage)
        key_before = (None if backend._dest_dense_cache is None
                      else backend._dest_dense_cache[0])
        backend._dest_dense()                 # what the interval does first
        cache_key, _, host = backend._dest_dense_cache
        if cache_key == key_before:           # a hit: the pulled copy stays
            assert host is host_prev
            hits += 1
        else:                                 # a refresh: nothing pulled yet
            assert host is None
            refreshed += 1
        stage.process_interval_arrays(keys)
        host = backend._dest_dense_cache[2]
        assert host.dtype == np.int64
        np.testing.assert_array_equal(host, expected)
        assert backend._dest_host() is host
        host_prev = host
    assert hits and refreshed


def test_restore_never_reads_a_stale_dest_copy():
    """``restore`` drops the dest cache, host copy and all. The controller's
    version rewinds on a restore, so two branches of one run can reach the
    same cache key (version, table size, capacity, domain, tasks) with
    different tables; after a restore onto one branch the stage must read
    that branch's table, never the host copy the other branch cached."""
    from repro.streams.checkpoint import checkpoint_stage, restore_stage

    stage = make_stage(WordCount(), "device", theta_max=0.05)
    rng = np.random.default_rng(0)
    keys = (rng.zipf(1.3, 5000) % 400).astype(np.int64)
    swapped = keys.copy()           # keys 1 and 2 trade their frequencies
    swapped[keys == 1], swapped[keys == 2] = 2, 1

    def table():
        return (stage.controller.assignment_version,
                dict(stage.controller.assignment.table))

    stage.process_interval_arrays(keys[:200] % 50)
    start = checkpoint_stage(stage)
    stage.process_interval_arrays(keys)               # branch A plans
    branch_a = checkpoint_stage(stage)
    version_a, table_a = table()
    restore_stage(stage, start)
    stage.process_interval_arrays(swapped)            # branch B plans
    version_b, table_b = table()
    assert version_a == version_b and len(table_a) == len(table_b)
    assert table_a != table_b
    stage.process_interval_arrays(keys)               # caches B's table
    stale = stage.backend._dest_dense_cache[2]

    restore_stage(stage, branch_a)
    assert stage.backend._dest_dense_cache is None
    assert table() == (version_a, table_a)
    expected = _route_dense_reference(stage)
    assert (stale != expected).any()
    stage.process_interval_arrays(keys)
    np.testing.assert_array_equal(stage.backend._dest_dense_cache[2],
                                  expected)


# -- backend selection + validation ------------------------------------------

def _hash32_controller(n_tasks=4, seed=0):
    return RebalanceController(Assignment(Hash32(n_tasks, seed=seed)),
                               BalanceConfig())


def test_device_backend_selection_rules():
    import jax

    class CustomOp(Operator):
        def process(self, store, interval, key, value):
            return [], 1.0

    # explicit requests
    assert make_stage(WordCount(), "device").state_backend == "device"
    with pytest.raises(ValueError, match="vectorized"):
        KeyedStage(WordCount(), _hash32_controller(), vectorized=False,
                   state_backend="device")
    with pytest.raises(ValueError, match="device closed forms"):
        KeyedStage(CustomOp(), _hash32_controller(), state_backend="device")
    with pytest.raises(ValueError, match="Hash32"):
        KeyedStage(WordCount(),
                   RebalanceController(Assignment(ModHash(4, seed=0)),
                                       BalanceConfig()),
                   state_backend="device")
    # auto only promotes to device on an accelerator backend; everywhere it
    # must still land on a working vectorized backend
    auto = KeyedStage(WordCount(), _hash32_controller())
    if jax.default_backend() == "cpu":
        assert auto.state_backend == "columnar"
    else:
        assert auto.state_backend == "device"
    # ModHash / non-device operators silently stay columnar or object
    assert KeyedStage(WordCount(),
                      RebalanceController(Assignment(ModHash(4, seed=0)),
                                          BalanceConfig())
                      ).state_backend == "columnar"
    assert KeyedStage(CustomOp(),
                      _hash32_controller()).state_backend == "object"


def test_device_rejects_out_of_domain_keys():
    stage = make_stage(WordCount(), "device", device_domain_max=1 << 12)
    with pytest.raises(ValueError, match="non-negative"):
        stage.process_interval_arrays(np.array([3, -1], dtype=np.int64),
                                      np.zeros(2))
    with pytest.raises(ValueError, match="device_domain_max"):
        stage.process_interval_arrays(np.array([1 << 12], dtype=np.int64),
                                      np.zeros(1))
    # in-range keys still work after the rejections (no partial mutation of
    # the interval counter would leave the ring clock skewed)
    stage.process_interval_arrays(np.array([5, 9], dtype=np.int64),
                                  np.zeros(2))
    assert stage.total_state_keys() == 2


@pytest.mark.parametrize("case", ["domain_grows", "moved_absent", "rescale",
                                  "raise_clears"])
def test_buffered_count_equals_isin(case):
    """The device backend counts the pause window's buffered tuples by a
    gather from a dense mask of the moved keys: that count reads as
    ``np.isin`` over the same prefix and as the object backend's report,
    when the interval grows the domain, when no moved key is in the prefix,
    after a rescale's large moved set, and after an interval that raised."""
    gen = WorkloadGen(k=400, z=1.1, f=0.8, seed=5, window=2)
    dev, obj = stages = [make_stage(WordCount(), b, window=2, theta_max=0.0)
                         for b in ("device", "object")]

    def run(keys, backends=stages):
        pause_hi = dev.pause_window(keys.size)
        moved = dev._pending_delta_arr
        want = 0 if pause_hi is None else int(
            np.isin(keys[:pause_hi], moved).sum())
        for stage in backends:
            stage.process_interval_arrays(keys, np.zeros(keys.size))
            assert stage.reports[-1].buffered == want, stage.state_backend
        return want

    def draw():
        gen.interval(dev.controller.assignment)
        return gen.draw_tuples(1000).astype(np.int64)

    for _ in range(3):
        run(draw())
    if case == "rescale":
        for stage in stages:
            stage.scale_to(7)
    moved = dev._pending_delta_arr
    assert moved is not None and moved.size
    keys = draw()
    pause_hi = dev.pause_window(keys.size)
    fleet = dev.backend._fleet
    domain = fleet.domain

    if case == "domain_grows":
        keys[0] = 4 * domain + 3
        keys[1] = moved[0]
        assert run(keys) > 0
        assert fleet.domain > domain
    elif case == "moved_absent":
        prefix = keys[:pause_hi]
        prefix[np.isin(prefix, moved)] = np.setdiff1d(keys, moved)[0]
        assert run(keys) == 0
    elif case == "rescale":
        assert moved.size > 20
        assert np.setdiff1d(moved, keys).size
        run(keys)
    else:
        keys[pause_hi - 1] = -1
        with pytest.raises(ValueError, match="non-negative"):
            dev.process_interval_arrays(keys, np.zeros(keys.size))
        assert dev._pending_delta_arr is None
        assert run(draw(), backends=[dev]) == 0


def test_device_max_mode_rejects_out_of_int32_values():
    stage = make_stage(MergeCounts(), "device")
    with pytest.raises(ValueError, match="int32"):
        stage.process_interval_arrays(np.array([1], dtype=np.int64),
                                      np.array([1 << 40]))


def test_device_empty_intervals_and_eviction():
    """n==0 intervals still advance the ring clock and expire columns."""
    stages = [make_stage(WordCount(), b, window=2)
              for b in ("device", "object")]
    for stage in stages:
        stage.process_interval_arrays(np.array([1, 2, 3], dtype=np.int64),
                                      np.zeros(3))
        for _ in range(3):                       # idle intervals: state ages out
            stage.process_interval_arrays(np.zeros(0, dtype=np.int64),
                                          np.zeros(0))
    assert_stages_identical(*stages)
    assert stages[0].total_state_keys() == 0

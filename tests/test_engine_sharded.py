"""Sharded (multi-device) state backend == dict state backend, observationally.

``state_backend='sharded'`` block-shards the dense device ring across a JAX
mesh and realizes the paper's mixed routing as a masked ``all_to_all``
inside ONE jitted ``shard_map`` step per interval. That is a pure placement
change: under the same streams, rebalances, window>1 eviction and mid-run
rescales it must produce the bit-identical :class:`IntervalReport` stream,
the same post-migration ``key_location`` map, and the same outputs/emit
streams as the object-store oracle — the Hypothesis property drives
randomized workloads through both backends in lockstep, mirroring
``tests/test_engine_device.py``.

The suite adapts to the available device count: the default tier-1 run has
one jax CPU device (a 1-shard mesh — the collectives still execute), while
the dedicated CI leg runs under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the all_to_all
crosses 8 real device boundaries. A cross-shard-count test additionally
pins that the shard count itself is observationally invisible (including a
block size that does NOT divide the domain).

The retrace test pins the compile-once contract: one trace per mode's step
across intervals and rebalances (the dense dest table is data, not shape),
and a route refresh recompile only when ``n_dest`` changes (scale_to).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core import Assignment, BalanceConfig, ModHash, RebalanceController
from repro.core.balancer.hashing import Hash32
from repro.streams import (KeyedStage, MergeCounts, Operator, PartialWordCount,
                           WindowedSelfJoin, WordCount, WorkloadGen)

N_SHARDS = min(8, jax.device_count())

REPORT_FIELDS = ("interval", "tuples", "makespan", "migration_stall",
                 "throughput", "skewness", "theta", "migrated_bytes",
                 "table_size", "buffered")


def make_stage(op, backend, n_tasks=5, window=3, theta_max=0.05,
               table_max=300, seed=1, n_shards=N_SHARDS, **kwargs):
    controller = RebalanceController(
        Assignment(Hash32(n_tasks, seed=seed)),
        BalanceConfig(theta_max=theta_max, table_max=table_max,
                      window=window),
        algorithm="mixed")
    if backend != "sharded":
        n_shards = None
    return KeyedStage(op, controller, window=window, vectorized=True,
                      state_backend=backend, n_shards=n_shards, **kwargs)


def assert_stages_identical(shd, obj):
    assert len(shd.reports) == len(obj.reports)
    for rc, ro in zip(shd.reports, obj.reports):
        for field in REPORT_FIELDS:
            assert getattr(rc, field) == getattr(ro, field), field
        np.testing.assert_array_equal(rc.task_loads, ro.task_loads)
    assert shd.outputs == obj.outputs
    assert shd.emitted_sum == obj.emitted_sum
    assert shd.total_state_keys() == obj.total_state_keys()
    # identical post-migration ownership: every held key lives on the same
    # task under both backends (and exactly one task each)
    all_keys = set()
    for store in obj.stores:
        all_keys.update(store.keys)
    for k in all_keys:
        loc_s, loc_o = shd.key_location(k), obj.key_location(k)
        assert loc_s == loc_o, k
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
              for b in ("sharded", "object")]
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
def test_sharded_equals_object_store_fixed(seed, z, f, window, theta,
                                           op_kind, scale_step):
    """Deterministic instances of the property — run even without the
    optional hypothesis extra (bare envs, see ci.yml's bare-collect job)."""
    _check_property(seed, z, f, window, theta, op_kind, scale_step)


try:                                    # optional [test] extra
    from hypothesis import given, settings, strategies as st
except ImportError:                     # pragma: no cover - bare env
    pass
else:
    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           z=st.floats(0.6, 1.3),
           f=st.floats(0.0, 1.2),
           window=st.integers(2, 4),
           theta=st.sampled_from([0.0, 0.03, 0.2]),
           op_kind=st.sampled_from(["wordcount", "selfjoin"]),
           scale_step=st.sampled_from([None, 3, 7]))
    def test_sharded_equals_object_store_property(seed, z, f, window, theta,
                                                  op_kind, scale_step):
        _check_property(seed, z, f, window, theta, op_kind, scale_step)


def test_partial_wordcount_sharded_matches_object():
    gens = [WorkloadGen(k=350, z=1.0, f=0.6, seed=17, window=2)
            for _ in range(2)]
    stages = [make_stage(PartialWordCount(), b, window=2)
              for b in ("sharded", "object")]
    for i in range(4):
        for gen, stage in zip(gens, stages):
            if i:
                gen.interval(stage.controller.assignment)
            drawn = gen.draw_tuples(900).astype(np.int64)
            stage.process_interval_arrays(drawn, np.full(900, i))
    assert_stages_identical(*stages)


def test_merge_counts_sharded_matches_object():
    """max-mode folding (MergeCounts): the raw tuples travel the mesh
    through the masked all_to_all and fold by scatter-max on the owner."""
    rng = np.random.default_rng(3)
    stages = [make_stage(MergeCounts(), b, window=2)
              for b in ("sharded", "object")]
    for _ in range(4):
        keys = rng.integers(0, 150, size=1200).astype(np.int64)
        vals = rng.integers(1, 40, size=1200)
        for stage in stages:
            stage.process_interval_arrays(keys, vals)
    assert_stages_identical(*stages)


def test_shard_count_is_observationally_invisible():
    """1-shard vs N-shard meshes produce identical results — including a
    shard count whose block size does NOT divide the (power-of-two) dense
    domain, so the dead padding rows in the last block are exercised."""
    counts = sorted({1, min(3, jax.device_count()), N_SHARDS})
    gens = [WorkloadGen(k=600, z=1.05, f=0.7, seed=9, window=3)
            for _ in counts]
    stages = [make_stage(WordCount(), "sharded", n_shards=s, seed=4)
              for s in counts]
    for i in range(5):
        for gen, stage in zip(gens, stages):
            if i:
                gen.interval(stage.controller.assignment)
            drawn = gen.draw_tuples(1500).astype(np.int64)
            stage.process_interval_emits(drawn, np.full(1500, i))
        if i == 2:
            for stage in stages:
                stage.scale_to(8)
    for other in stages[1:]:
        assert_stages_identical(other, stages[0])


def test_sharded_with_pallas_substrate_matches_object():
    """The Pallas route does not run inside the sharded step, so the stage
    refuses substrate='pallas' instead of silently routing through the jnp
    twin; the same stream on the default substrate matches the oracle."""
    with pytest.raises(ValueError, match="substrate='numpy'"):
        make_stage(WordCount(), "sharded", substrate="pallas")
    gens = [WorkloadGen(k=300, z=1.0, f=0.5, seed=5, window=3)
            for _ in range(2)]
    stages = [make_stage(WordCount(), "sharded"),
              make_stage(WordCount(), "object")]
    for i in range(4):
        for gen, stage in zip(gens, stages):
            if i:
                gen.interval(stage.controller.assignment)
            drawn = gen.draw_tuples(500).astype(np.int64)
            stage.process_interval_arrays(drawn, np.full(500, i))
    assert_stages_identical(*stages)


def test_sharded_check_rejects_pallas_substrate():
    """The refusal lives in the backend's own check, before any mesh or
    fleet is built; the single-device backend still takes the kernel."""
    from repro.streams.backends import get_backend
    ctrl = _hash32_controller()
    with pytest.raises(ValueError, match="shard_map"):
        get_backend("sharded").check(WordCount(), ctrl, True, "pallas")
    get_backend("sharded").check(WordCount(), ctrl, True, "numpy")
    get_backend("device").check(WordCount(), ctrl, True, "pallas")


def test_sharded_checkpoint_clears_rows_on_make_mesh_mesh():
    """Regression: checkpointing extracts every held key (``clear_rows``
    scatters into the mesh-sharded ring) and reinstalls it. On a mesh with
    Explicit axes that scatter raised ShardingTypeError; make_mesh builds
    Auto axes, and the round trip must leave the ring bit-identical."""
    from jax.sharding import AxisType

    from repro.streams import checkpoint_stage

    stage = make_stage(WordCount(), "sharded", window=2)
    fleet = stage.backend._fleet
    assert set(fleet.mesh.axis_types) == {AxisType.Auto}
    rng = np.random.default_rng(8)
    stage.process_interval_arrays(rng.integers(0, 700, 900).astype(np.int64))
    before = [np.asarray(fleet.vals).copy(), np.asarray(fleet.pres).copy()]
    held = stage.total_state_keys()
    checkpoint_stage(stage)
    np.testing.assert_array_equal(np.asarray(fleet.vals), before[0])
    np.testing.assert_array_equal(np.asarray(fleet.pres), before[1])
    assert stage.total_state_keys() == held
    assert fleet.vals.sharding.spec == jax.sharding.PartitionSpec(None,
                                                                  "shard")
    # and a direct clear drops exactly that key's column
    row = int(np.nonzero(fleet.task[:fleet.domain] >= 0)[0][0])
    fleet.clear_rows(np.array([row]))
    assert fleet.task[row] == -1
    assert not fleet.host_state()[1][:, row].any()
    assert stage.total_state_keys() == held - 1


# -- compile-once: the sharded step must not retrace across intervals --------

def test_no_retrace_sharded():
    """The shard_map step traces once per mode and is reused for every
    subsequent interval — rebalances swap the (data, not shape) replicated
    table and relabel host mirrors, so they must not retrace; the sharded
    step carries no n_tasks static at all, so even ``scale_to`` leaves it
    alone. The per-shard route refresh recompiles exactly once per
    ``n_dest`` change (rescale)."""
    from repro.streams import sharded as sh_mod

    # the sharded jit wrappers are per-fleet (not module-level), so a fresh
    # stage always contributes exactly its own traces to the counters
    base = dict(sh_mod.TRACE_COUNTS)
    stage = make_stage(WordCount(), "sharded", n_tasks=6, window=5,
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
    d6 = {k: sh_mod.TRACE_COUNTS[k] - base[k] for k in base}
    assert d6["interval_step"] == 1, d6
    assert d6["route_dense"] == 1, d6

    stage.scale_to(9)
    for i in range(6, 10):
        gen.interval(stage.controller.assignment)
        stage.process_interval_arrays(gen.draw_tuples(1000).astype(np.int64),
                                      np.full(1000, i))
    d10 = {k: sh_mod.TRACE_COUNTS[k] - base[k] for k in base}
    assert d10["interval_step"] == 1, d10
    assert d10["route_dense"] == 2, d10


# -- backend selection + validation ------------------------------------------

def _hash32_controller(n_tasks=4, seed=0):
    return RebalanceController(Assignment(Hash32(n_tasks, seed=seed)),
                               BalanceConfig())


def test_sharded_backend_selection_rules():
    class CustomOp(Operator):
        def process(self, store, interval, key, value):
            return [], 1.0

    # explicit request works and reports its name
    stage = make_stage(WordCount(), "sharded")
    assert stage.state_backend == "sharded"
    assert stage.backend._fleet.n_shards == N_SHARDS
    # sharded inherits every device requirement, with its own name in the
    # errors
    with pytest.raises(ValueError, match="vectorized"):
        KeyedStage(WordCount(), _hash32_controller(), vectorized=False,
                   state_backend="sharded")
    with pytest.raises(ValueError, match="device closed forms"):
        KeyedStage(CustomOp(), _hash32_controller(), state_backend="sharded")
    with pytest.raises(ValueError, match="Hash32"):
        KeyedStage(WordCount(),
                   RebalanceController(Assignment(ModHash(4, seed=0)),
                                       BalanceConfig()),
                   state_backend="sharded")
    # explicit-only: auto never lands on sharded (device/columnar/object
    # cover auto; the shard count is a launcher decision)
    assert KeyedStage(WordCount(),
                      _hash32_controller()).state_backend != "sharded"
    # shard counts beyond the local device fleet fail loudly
    with pytest.raises(ValueError, match="n_shards"):
        KeyedStage(WordCount(), _hash32_controller(),
                   state_backend="sharded",
                   n_shards=jax.device_count() + 1)


def test_sharded_rejects_out_of_domain_keys():
    stage = make_stage(WordCount(), "sharded", device_domain_max=1 << 12)
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


def test_sharded_max_mode_rejects_out_of_int32_values():
    stage = make_stage(MergeCounts(), "sharded")
    with pytest.raises(ValueError, match="int32"):
        stage.process_interval_arrays(np.array([1], dtype=np.int64),
                                      np.array([1 << 40]))


def test_sharded_empty_intervals_and_eviction():
    """n==0 intervals still advance the ring clock and expire columns."""
    stages = [make_stage(WordCount(), b, window=2)
              for b in ("sharded", "object")]
    for stage in stages:
        stage.process_interval_arrays(np.array([1, 2, 3], dtype=np.int64),
                                      np.zeros(3))
        for _ in range(3):                       # idle intervals: state ages out
            stage.process_interval_arrays(np.zeros(0, dtype=np.int64),
                                          np.zeros(0))
    assert_stages_identical(*stages)
    assert stages[0].total_state_keys() == 0

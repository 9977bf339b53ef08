"""The engine's host spans and counters (``repro.core.telemetry``), read
back from a ``jax.profiler`` trace of a few intervals that plan and migrate,
on the device backend (Pallas route interpreted) and the sharded backend."""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core import Assignment, BalanceConfig, RebalanceController
from repro.core.balancer.hashing import Hash32
from repro.core.telemetry import COUNTERS, SPANS, count, span
from repro.streams import KeyedStage, WordCount
from repro.streams.generator import WorkloadGen

ENTRY = "entry"          # the test's own span around each entry call
N_TASKS, WINDOW, TABLE_MAX, TUPLES = 6, 2, 100, 20_000
WARM, TRACED = 2, 5
WATCHED = ("d2h_bytes", "h2d_bytes", "route_refreshes", "plans",
           "migrated_keys")


def _stage(backend: str) -> KeyedStage:
    controller = RebalanceController(
        Assignment(Hash32(N_TASKS, seed=7)),
        BalanceConfig(theta_max=0.03, table_max=TABLE_MAX, window=WINDOW),
        algorithm="mixed")
    substrate = "pallas" if backend == "device" else "numpy"
    return KeyedStage(WordCount(), controller, window=WINDOW,
                      state_backend=backend, substrate=substrate,
                      kernel_interpret=True)


def _host_events(trace_dir):
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    plane = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    wanted = set(SPANS) | {ENTRY}
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for line in plane.lines for e in line.events if e.name in wanted]


@pytest.fixture(scope="module", params=["device", "sharded"])
def traced(request, tmp_path_factory):
    """A stage run for a few warm intervals, then traced for a few more:
    its host events, the counters' change over the traced intervals, and
    the plans made there."""
    stage = _stage(request.param)
    gen = WorkloadGen(k=600, z=1.1, f=0.8, seed=3, window=WINDOW)
    batches = []
    for i in range(WARM + TRACED):
        if i:
            gen.interval(stage.controller.assignment)
        batches.append(gen.draw_tuples(TUPLES).astype(np.int64))
    for keys in batches[:WARM]:
        stage.process_interval_arrays(keys)
    before = {k: COUNTERS.get(k, 0) for k in WATCHED}
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(trace_dir)
    try:
        for keys in batches[WARM:]:
            with TraceAnnotation(ENTRY):
                stage.process_interval_arrays(keys)
    finally:
        jax.profiler.stop_trace()
    delta = {k: COUNTERS.get(k, 0) - before[k] for k in WATCHED}
    plans = [ev.result for ev in stage.controller.history
             if ev.triggered and ev.interval > WARM]
    return stage, _host_events(trace_dir), delta, plans


def _named(events, name):
    return [ev for ev in events if ev[0] == name]


def test_every_span_is_recorded(traced):
    stage, events, delta, plans = traced
    assert {ev[0] for ev in events} >= set(SPANS)
    assert len(_named(events, ENTRY)) == TRACED
    assert delta["plans"] == len(plans) > 0


def test_plan_and_migrate_inside_one_entry_call(traced):
    _, events, _, plans = traced
    entries = _named(events, ENTRY)

    def entry_of(ev):
        inside = [i for i, (_, s, e, _) in enumerate(entries)
                  if s <= ev[1] and ev[2] <= e]
        assert len(inside) == 1, ev
        return inside[0]

    migrates = _named(events, "migrate")
    assert len(migrates) == sum(len(p.moved_keys) > 0 for p in plans) > 0
    for mig in migrates:
        plan = max((p for p in _named(events, "plan") if p[2] <= mig[1]),
                   key=lambda p: p[1])
        assert entry_of(plan) == entry_of(mig)


def test_pull_bytes_match_the_counter_and_the_domain(traced):
    stage, events, delta, _ = traced
    pulls = _named(events, "pull")
    assert sum(ev[3]["bytes"] for ev in pulls) == delta["d2h_bytes"]
    # one pull of a per-key int32 array, and the pulls of one interval:
    # the device backend keeps its histogram on the host, the sharded one
    # pulls it too, in the mesh's padded layout
    fleet = stage.backend._fleet
    if stage.state_backend == "sharded":
        width, per_interval = fleet.n_shards * (fleet._block + 1) * 4, 5
    else:
        width, per_interval = (fleet.domain + 1) * 4, 4
    assert delta["d2h_bytes"] == width * (
        per_interval * TRACED + delta["route_refreshes"])
    assert len(_named(events, "route")) == delta["route_refreshes"] > 0


def test_upload_bytes_match_the_shapes(traced):
    stage, _, delta, _ = traced
    fleet = stage.backend._fleet
    w1 = WINDOW + 1
    table = 2 * stage._table_capacity * 4        # keys and dests, int32
    if stage.state_backend == "sharded":
        s = fleet.n_shards                        # replicated operands
        per_interval = s * fleet._chunk_cap * 4 + 2 * s * w1 * 4
        per_refresh = s * table
    else:
        per_interval = (fleet.domain + 1) * 4 + 2 * w1 * 4
        per_refresh = table
    assert stage._table_capacity == 128           # one table shape
    assert delta["h2d_bytes"] == (per_interval * TRACED
                                  + per_refresh * delta["route_refreshes"])


def test_migrated_keys_count_the_plans_moves(traced):
    _, _, delta, plans = traced
    assert delta["migrated_keys"] == sum(len(p.moved_keys) for p in plans)


def test_plan_span_times_the_plan(traced):
    _, events, _, plans = traced
    span_s = sum(e - s for _, s, e, _ in _named(events, "plan")) * 1e-9
    timed_s = sum(p.plan_time_s for p in plans)
    assert span_s == pytest.approx(timed_s, rel=0.10)


def test_counters_and_spans_without_a_session():
    before = COUNTERS.get("test.telemetry", 0)
    count("test.telemetry")
    count("test.telemetry", 4)
    assert COUNTERS["test.telemetry"] == before + 5
    with span("pull", bytes=8):       # no profiler session: a no-op
        pass
    assert len(set(SPANS)) == len(SPANS) == 9

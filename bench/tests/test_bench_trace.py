"""The trace reduction, on a trace recorded on one TPU v5e: a short traced
run of the keyed stage at 2^14 keys and 300,000 tuples an interval, whose
result line gave ``busy_s`` 0.0022367100000000003 and ``window_s``
0.26193958300000003."""

import gzip
from pathlib import Path

import pytest
from jax.profiler import ProfileData

import core
import tracereduce

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def summary():
    raw = gzip.open(DATA / "wordcount_drift_tiny.xplane.pb.gz").read()
    return tracereduce.reduce(ProfileData.from_serialized_xspace(raw),
                              core.SPANS)


def test_busy_and_window_as_the_chip_run_read_them(summary):
    assert summary.busy_s == pytest.approx(0.0022367100000000003, rel=1e-12)
    assert summary.window_s == pytest.approx(0.26193958300000003, rel=1e-12)
    assert 0.0 < summary.busy_s < summary.window_s
    assert summary.idle_share == pytest.approx(1 - 0.0022367100000000003
                                               / 0.26193958300000003)


def test_programs_and_ops(summary):
    calls, seconds = summary.program_seconds("jit__interval_step_add")
    assert calls == 21 and seconds == pytest.approx(0.000117893, rel=1e-9)
    calls, seconds = summary.program_seconds("jit__routing_lookup")
    assert calls == 17 and seconds == pytest.approx(0.002125384, rel=1e-9)
    assert summary.program_seconds("jit__nothing") == (0, 0.0)
    names = [n for n, _ in summary.ops]
    assert names[0] == "jit__routing_lookup:%_routing_lookup.1"
    assert len(names) <= 10
    # ops inside programs never add up to more than the busy time
    assert sum(s for _, s in summary.ops) <= summary.busy_s + 1e-12


def test_idle_gaps_are_named_by_harness_spans(summary):
    assert len(summary.idle_gaps) == 10
    assert {n for n, _ in summary.idle_gaps} <= {"interval", "source", "none"}
    lengths = [s for _, s in summary.idle_gaps]
    assert lengths == sorted(lengths, reverse=True)
    assert lengths[0] == pytest.approx(0.012493501, rel=1e-9)


def test_union_clip_and_gaps():
    busy = tracereduce.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert tracereduce.clip(busy, 1, 6) == [(1, 3), (5, 6)]
    assert tracereduce.gaps(busy, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert tracereduce.program_name("jit__f(123)") == "jit__f"
    assert tracereduce.op_name("%fusion.1 = s32[4] fusion(x)") == "%fusion.1"


def test_window_span_is_found_once(summary):
    raw = gzip.open(DATA / "wordcount_drift_tiny.xplane.pb.gz").read()
    profile = ProfileData.from_serialized_xspace(raw)
    (name, start, end), = tracereduce.host_spans(profile, ["window"])
    assert (end - start) * 1e-9 == pytest.approx(summary.window_s)
    assert tracereduce.host_spans(profile, ["no-such-span"]) == []

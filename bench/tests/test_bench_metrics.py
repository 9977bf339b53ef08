"""The metric readers, on a run whose numbers are known."""

import gzip
import json
from pathlib import Path

import pytest
from jax.profiler import ProfileData

import core
import spanreduce
import tracereduce

DATA = Path(__file__).resolve().parent / "data"
PEAKS = json.loads((core.BENCH / "peaks.json").read_text())


def fake_run(cell="wordcount.drift.sat", intervals=(), **kw):
    """A run of a paper-wordcount cell on the traffic its name ends in."""
    traffic = cell.split(".", 1)[1]
    cfg = json.loads((core.BENCH / "configs" / "paper-wordcount.json")
                     .read_text())
    c = core.Cell(cell, 1, cfg, traffic, [], [])
    mix = __import__("source").Mix.load(traffic)
    return core.Run(c, mix, setup_s=12.5, t0=0.0,
                    t_close=kw.pop("t_close", 10.0), intervals=list(intervals),
                    **kw)


def read(name, run):
    return core.read_metrics(run, [{"name": name, "unit": "x"}]).get(
        name, {}).get("value")


def test_throughput_counts_only_completed_intervals():
    ivs = [core.Interval(100, due=0, handed=0, done=1),
           core.Interval(100, due=1, handed=1)]
    run = fake_run("wordcount.drift.sat", ivs, t_close=2.0)
    assert read("throughput_tps", run) == 50.0
    assert read("setup_s", run) == 12.5
    assert read("interval_ms.sat", run) == 1000.0


def test_rooflines_from_the_recorded_trace():
    """The tiny traced run on the chip printed these shares (its table had
    grown to the 1024-entry capacity)."""
    raw = gzip.open(DATA / "wordcount_drift_tiny.xplane.pb.gz").read()
    summary = tracereduce.reduce(ProfileData.from_serialized_xspace(raw),
                                 core.SPANS)
    run = fake_run("wordcount.drift.sat", summary=summary,
                   peaks=PEAKS["devices"]["TPU v5 lite"],
                   kernel_shapes={
                       "interval_step_add": {"window": 4, "keys": 16385},
                       "routing_lookup": {"keys": 16385, "table": 1024}})
    assert read("interval_step_add_roofline", run) == pytest.approx(
        35.63726951884009, rel=1e-12)
    assert read("routing_lookup_roofline", run) == pytest.approx(
        0.1360164466690158, rel=1e-12)
    assert read("device_idle_pct.sat", run) == pytest.approx(
        99.14609698374606, rel=1e-12)


def test_device_readers_are_silent_without_a_trace():
    run = fake_run("wordcount.drift.sat")
    for name in ("device_idle_pct.sat", "interval_step_add_roofline",
                 "routing_lookup_roofline"):
        assert read(name, run) is None


SPAN_READERS = {"route_ms.sat": ("route",), "step_host_ms.sat": ("step",),
                "pull_ms.sat": ("pull",), "finish_ms.sat": ("finish",),
                "stats_ms.sat": ("stats", "trigger"),
                "migrate_ms.sat": ("migrate", "pause"),
                "untraced_ms.sat": ("untraced",)}


@pytest.mark.parametrize("name", sorted(SPAN_READERS) + ["d2h_kb.sat",
                                                         "h2d_kb.sat"])
def test_span_and_counter_readers(name):
    """Per completed interval: the self times of the reader's spans in ms,
    a counter's change in kB; silent without a trace, or where the program
    has no such span or counter."""
    ivs = [core.Interval(10, due=0, handed=0, done=1)] * 4 \
        + [core.Interval(10, due=4, handed=4)]
    seconds = {n: 0.001 * (i + 1) for i, n in enumerate(
        ("pause", "route", "step", "pull", "finish", "stats", "trigger",
         "plan", "migrate", "untraced"))}
    spans = spanreduce.SpanSummary(seconds, {})
    counters = {"d2h_bytes": 1_310_800, "h2d_bytes": 349_600, "plans": 4}
    run = fake_run(intervals=ivs, spans=spans, counters=counters)
    if name in SPAN_READERS:
        want = 1e3 * sum(seconds[n] for n in SPAN_READERS[name]) / 4
    else:
        want = counters[name.replace("_kb.sat", "_bytes")] / 1e3 / 4
    assert read(name, run) == pytest.approx(want, rel=1e-12)
    assert read(name, fake_run(intervals=ivs)) is None
    lacking = fake_run(intervals=ivs, counters={"plans": 4}, spans=(
        spanreduce.SpanSummary({"plan": 0.1}, {})))
    assert read(name, lacking) is None

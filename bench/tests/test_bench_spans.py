"""The program-span reduction (``spanreduce``) and the gap cutting
(``tracereduce.cut_gaps``): self times, gap cutting and naming on a small
trace recorded on the CPU with nested spans, the idle gaps of the
checked-in chip trace, and a tiny traced run of a cell."""

import glob
import gzip
import time
from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData, TraceAnnotation

import core
import spanreduce
import tiny
import tracereduce
from repro.core.telemetry import SPANS

#: the per-layer metrics read from program spans and counters, without
#: their cell suffix
SPAN_METRICS = ("route_ms", "step_host_ms", "pull_ms", "finish_ms",
                "stats_ms", "migrate_ms", "untraced_ms")
COUNTER_METRICS = ("d2h_kb", "h2d_kb")

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Two intervals, each: an untraced stretch, ``route`` holding a
    ``pull``, then ``step``; returns the profile and its events by name."""
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(trace_dir)
    try:
        with TraceAnnotation("window"):
            for _ in range(2):
                with TraceAnnotation("interval"):
                    time.sleep(0.002)
                    with TraceAnnotation("route", table=128):
                        time.sleep(0.002)
                        with TraceAnnotation("pull", bytes=4096):
                            time.sleep(0.002)
                        time.sleep(0.001)
                    with TraceAnnotation("step"):
                        time.sleep(0.003)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    profile = ProfileData.from_file(path)
    events = {}
    for name, s, e in tracereduce.host_spans(
            profile, ["window", "interval", "route", "pull", "step"]):
        events.setdefault(name, []).append((s, e))
    return profile, {n: sorted(v) for n, v in events.items()}


def _length(spans):
    return sum(e - s for s, e in spans) * 1e-9


def test_self_times_and_untraced_add_up_to_the_intervals(recorded):
    profile, ev = recorded
    sec = spanreduce.span_seconds(profile, SPANS)
    assert sec["pull"] == pytest.approx(_length(ev["pull"]), abs=1e-12)
    assert sec["route"] == pytest.approx(
        _length(ev["route"]) - _length(ev["pull"]), abs=1e-12)
    assert sec["step"] == pytest.approx(_length(ev["step"]), abs=1e-12)
    assert sec["plan"] == 0.0
    untraced = _length(ev["interval"]) - _length(ev["route"]) \
        - _length(ev["step"])
    assert sec["untraced"] == pytest.approx(untraced, abs=1e-12)
    assert sec["untraced"] > 0.003            # the two sleeps before route
    assert sum(sec.values()) == pytest.approx(_length(ev["interval"]),
                                              abs=1e-12)
    stats = spanreduce.span_stats(profile, SPANS)
    assert stats == {"pull": {"bytes": 8192}, "route": {"table": 256}}


def test_span_seconds_clip_to_a_window(recorded):
    profile, ev = recorded
    (lo, _), (_, hi) = ev["interval"]
    first = spanreduce.span_seconds(profile, SPANS, lo=lo,
                                    hi=ev["interval"][0][1])
    both = spanreduce.span_seconds(profile, SPANS, lo=lo, hi=hi)
    assert first["step"] == pytest.approx(_length(ev["step"][:1]), abs=1e-12)
    assert both["step"] == pytest.approx(_length(ev["step"]), abs=1e-12)
    assert spanreduce.span_stats(profile, SPANS, lo=lo,
                                 hi=ev["interval"][0][1]) \
        == {"pull": {"bytes": 4096}, "route": {"table": 128}}


def test_gaps_are_cut_and_named_after_the_innermost_span(recorded):
    profile, ev = recorded
    (r0, r1), (p0, p1), (s0, s1) = (ev["route"][0], ev["pull"][0],
                                    ev["step"][0])
    (i0, _), (_, w1) = ev["interval"][0], ev["window"][0]
    gaps = [(i0, r0),                          # before any program span
            ((r0 + p0) / 2, s0 + 1000),        # route, pull, route, -, step
            (w1 + 1000, w1 + 3000)]            # outside every harness span
    spans = tracereduce.Spans.of(profile, SPANS, core.SPANS)
    pieces = tracereduce.cut_gaps(gaps, spans)
    assert [n for n, _ in pieces] == ["interval", "route", "pull", "route",
                                      "interval", "step", "none"]
    ns = [s * 1e9 for _, s in pieces]
    assert ns[1] == pytest.approx(p0 - (r0 + p0) / 2)
    assert ns[2] == pytest.approx(p1 - p0)
    assert ns[3] == pytest.approx(r1 - p1)
    assert ns[4] == pytest.approx(s0 - r1)
    assert ns[5] == pytest.approx(1000)
    # the pieces of each gap add up to the gap
    assert sum(s for _, s in pieces) == pytest.approx(
        sum(e - s for s, e in gaps) * 1e-9, abs=1e-12)
    assert ns[0] == pytest.approx(r0 - i0)
    assert ns[6] == pytest.approx(2000)


def test_chip_trace_without_program_spans_keeps_its_gaps():
    """The checked-in trace predates the program's spans: every piece is a
    whole gap, named by its harness span, and the pieces of every chip's
    gaps add up to its idle time."""
    raw = gzip.open(DATA / "wordcount_drift_tiny.xplane.pb.gz").read()
    profile = ProfileData.from_serialized_xspace(raw)
    harness_only = tracereduce.reduce(profile, core.SPANS)
    cut = tracereduce.reduce(profile, core.SPANS, SPANS)
    every = tracereduce.reduce(profile, core.SPANS, SPANS, top=10**9)
    assert cut.idle_gaps == harness_only.idle_gaps == every.idle_gaps[:10]
    assert sum(s for _, s in every.idle_gaps) == pytest.approx(
        every.window_s - every.busy_s, rel=1e-9)
    assert {n for n, _ in every.idle_gaps} <= {"interval", "source", "none"}
    spans = spanreduce.reduce(profile, SPANS)
    assert all(spans.seconds[n] == 0.0 for n in SPANS)
    assert spans.seconds["untraced"] > 0
    assert spans.stats == {}


def test_tiny_traced_run_reports_every_layer_metric():
    """``core.run_cell`` traced: the program spans' and counters' metrics
    are in the result line, their bytes follow from the shapes, and the
    span metrics with the plan's self time add up to the intervals'."""
    result, run = tiny.run("wordcount.drift.sat", trace=True)
    assert result["correct"]
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert {f"{k}.sat" for k in SPAN_METRICS + COUNTER_METRICS} <= set(m)
    n, c = len(run.done()), run.counters
    assert n > 0 and all(m[f"{k}.sat"] >= 0 for k in SPAN_METRICS)
    # every byte pulled is a pull span's: one stacked (4, D+1) int32 copy
    # of the step's outputs an interval and one (D+1,) dest table a route
    # refresh
    stats = run.spans.stats
    width = (16384 + 1) * 4
    assert c["d2h_bytes"] == stats["pull"]["bytes"] == width * (
        4 * n + c["route_refreshes"])
    # the histogram and two (w+1,) column masks an interval, keys and
    # dests at the table's capacity a refresh
    assert c["h2d_bytes"] == n * (width + 2 * 2 * 4) \
        + 2 * 4 * stats["route"]["table"]
    assert m["d2h_kb.sat"] == c["d2h_bytes"] / 1e3 / n
    assert m["h2d_kb.sat"] == c["h2d_bytes"] / 1e3 / n
    assert c["plans"] > 0 and c["route_refreshes"] > 0
    # the seven span metrics and the plan's self time add up to the
    # interval spans' time, which the host clock around each call holds
    sec = run.spans.seconds
    assert sec["plan"] > 0 and sec["untraced"] < 0.25 * sum(sec.values())
    spans_ms = sum(m[f"{k}.sat"] for k in SPAN_METRICS) + 1e3 * sec["plan"] / n
    assert spans_ms == pytest.approx(1e3 * sum(sec.values()) / n, rel=1e-9)
    host_ms = 1e3 * sum(iv.done - iv.handed for iv in run.done()) / n
    assert 0.9 * host_ms <= spans_ms <= host_ms

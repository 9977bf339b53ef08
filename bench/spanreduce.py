#!/usr/bin/env python3
"""The program's host spans and counters in a traced run of a cell.

The program marks its host phases with spans (``repro.core.telemetry``:
``jax.profiler.TraceAnnotation``s on the ``/host:CPU`` plane, on the device
trace's clock) and counts its device transfers. This module reads them:

* :func:`span_seconds`: each program span's self time (its length less the
  part its child program spans cover), clipped to a window, and
  ``untraced``: the time of each harness ``interval`` span that no program
  span covers. The self times and ``untraced`` add up to the intervals'
  time. It needs no device plane;
* :func:`cut_gaps`: the chip's idle gaps cut at program-span boundaries,
  each piece named after the innermost program span open there, or after
  the harness span that overlaps it most where none is; :func:`idle_by_span`
  totals the pieces per name;
* :func:`layer_metrics`: the per-interval numbers of the engine's host
  layers, from the two above and the counters' change over the window.

Run as a script, it runs one cell as ``run.py --trace 0`` does and prints
the result line, then one more line with these numbers:

    python3 bench/spanreduce.py --workload <cell> --seed <n> --seconds <s>
                                --trace <0|1>

``core.run_cell`` deletes its trace before the metric readers run and
takes no counters, so the script wraps ``core.run_window``: the wrapper
snapshots the counters around the window and, with ``--trace 1``, records
its own trace of it. This is a stopgap: once ``core.run_cell`` and
``tracereduce`` take the span reduction and the counter snapshot
themselves, the wrapper goes.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import (Dict, Iterable, List, Optional, Sequence,  # noqa: E402
                    Tuple)

import core  # noqa: E402
import tracereduce  # noqa: E402

if str(core.ROOT / "src") not in sys.path:
    sys.path.insert(0, str(core.ROOT / "src"))
try:
    from repro.core.telemetry import COUNTERS  # noqa: E402
    from repro.core.telemetry import SPANS as PROGRAM_SPANS  # noqa: E402
except ImportError:                        # a program without telemetry
    COUNTERS, PROGRAM_SPANS = None, ()
INTERVAL_SPAN = "interval"
UNTRACED = "untraced"

#: per-interval metric -> the program spans whose self time it sums
SPAN_METRICS = {
    "route_ms": ("route",),
    "step_host_ms": ("step",),
    "pull_ms": ("pull",),
    "finish_ms": ("finish",),
    "stats_ms": ("stats", "trigger"),
    "migrate_ms": ("migrate", "pause"),
    "untraced_ms": (UNTRACED,),
}
#: per-interval metric -> the counter whose change it reads, in KB
COUNTER_METRICS = {"d2h_kb": "d2h_bytes", "h2d_kb": "h2d_bytes"}
#: counters printed per interval as they are
COUNTS_PER_INTERVAL = ("route_refreshes", "plans", "migrated_keys")

Span = Tuple[str, float, float]                 # name, start ns, end ns


@dataclasses.dataclass
class Spans:
    """Program and harness spans of a trace's host plane, each list sorted
    by start; one program span list per thread line (spans nest there)."""

    program: List[List[Span]]
    harness: List[Span]

    @classmethod
    def of(cls, profile, program_names: Sequence[str] = PROGRAM_SPANS,
           harness_names: Sequence[str] = ("source", INTERVAL_SPAN)
           ) -> "Spans":
        plane = profile.find_plane_with_name(tracereduce.HOST_PLANE)
        program, harness = [], []
        wanted, outer = set(program_names), set(harness_names)
        for line in (plane.lines if plane is not None else ()):
            mine = []
            for e in line.events:
                t = (e.name, float(e.start_ns),
                     float(e.start_ns + e.duration_ns))
                if e.name in wanted:
                    mine.append(t)
                elif e.name in outer:
                    harness.append(t)
            if mine:
                program.append(sorted(mine, key=lambda t: (t[1], -t[2])))
        return cls(program, sorted(harness, key=lambda t: t[1]))


def _clip(spans: Iterable[Span], lo: float, hi: float) -> List[Span]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in spans
            if e > lo and s < hi]


def segments(spans: Sequence[Span]) -> List[Span]:
    """The stretches of a line's nested spans (sorted by start, then by
    end descending), each named after the innermost span open there; the
    stretches no span covers are left out."""
    out: List[Span] = []
    stack: List[Tuple[str, float]] = []
    cur = 0.0
    for name, s, e in spans:
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            out.append((top, cur, end))
            cur = end
        if stack:
            out.append((stack[-1][0], cur, s))
        stack.append((name, e))
        cur = s
    while stack:
        top, end = stack.pop()
        out.append((top, cur, end))
        cur = end
    return [t for t in out if t[2] > t[1]]


def span_seconds(profile, names: Sequence[str] = PROGRAM_SPANS,
                 lo: float = float("-inf"), hi: float = float("inf")
                 ) -> Dict[str, float]:
    """Self seconds of each program span of ``names`` inside ``[lo, hi)``
    (ns on the trace's clock), and under ``"untraced"`` the seconds of the
    harness ``interval`` spans there that no program span covers."""
    spans = Spans.of(profile, names)
    out = {n: 0.0 for n in names}
    covered: List[Tuple[float, float]] = []
    for line in spans.program:
        for name, s, e in segments(_clip(line, lo, hi)):
            out[name] += (e - s) * 1e-9
            covered.append((s, e))
    covered = tracereduce.union(covered)
    untraced = 0.0
    for _, s, e in _clip((t for t in spans.harness
                          if t[0] == INTERVAL_SPAN), lo, hi):
        inside = tracereduce.clip(covered, s, e)
        untraced += (e - s - sum(b - a for a, b in inside)) * 1e-9
    out[UNTRACED] = untraced
    return out


def span_stats(profile, names: Sequence[str] = PROGRAM_SPANS,
               lo: float = float("-inf"), hi: float = float("inf")
               ) -> Dict[str, Dict[str, int]]:
    """Per program span name, the sum of each integer stat of its events
    that start inside ``[lo, hi)`` (a ``pull``'s ``bytes``)."""
    plane = profile.find_plane_with_name(tracereduce.HOST_PLANE)
    out: Dict[str, Dict[str, int]] = {}
    wanted = set(names)
    for line in (plane.lines if plane is not None else ()):
        for e in line.events:
            if e.name not in wanted or not lo <= e.start_ns < hi:
                continue
            for key, value in e.stats:
                if isinstance(value, int):
                    sums = out.setdefault(e.name, {})
                    sums[key] = sums.get(key, 0) + value
    return out


def _harness_name(harness: Sequence[Span], starts: Sequence[float],
                  a: float, b: float) -> str:
    """The harness span overlapping ``[a, b)`` most, as ``tracereduce``
    names a gap; ``"none"`` where none does."""
    best, overlap = "none", 0.0
    for name, s, e in harness[max(0, bisect.bisect_right(starts, a) - 1):
                              bisect.bisect_left(starts, b)]:
        o = min(e, b) - max(s, a)
        if o > overlap:
            best, overlap = name, o
    return best


def cut_gaps(gaps: Sequence[Tuple[float, float]], spans: Spans
             ) -> List[Tuple[str, float]]:
    """Each idle gap (ns, sorted, disjoint) cut at program-span boundaries:
    ``(name, seconds)`` per piece, in time order. A piece is named after
    the innermost program span open there, else after the harness span
    that overlaps it most."""
    segs = sorted((t for line in spans.program for t in segments(line)),
                  key=lambda t: t[1])
    seg_starts = [s for _, s, _ in segs]
    harness_starts = [s for _, s, _ in spans.harness]
    out: List[Tuple[str, float]] = []
    for gs, ge in gaps:
        cur = gs
        i = max(0, bisect.bisect_right(seg_starts, gs) - 1)
        for name, s, e in segs[i:bisect.bisect_left(seg_starts, ge)]:
            s, e = max(s, cur), min(e, ge)
            if e <= s:
                continue
            if s > cur:
                out.append((_harness_name(spans.harness, harness_starts,
                                          cur, s), (s - cur) * 1e-9))
            out.append((name, (e - s) * 1e-9))
            cur = e
        if ge > cur:
            out.append((_harness_name(spans.harness, harness_starts,
                                      cur, ge), (ge - cur) * 1e-9))
    return out


def idle_by_span(pieces: Iterable[Tuple[str, float]]
                 ) -> List[Tuple[str, float]]:
    """Idle seconds per name, most first."""
    total: Dict[str, float] = {}
    for name, seconds in pieces:
        total[name] = total.get(name, 0.0) + seconds
    return sorted(total.items(), key=lambda t: -t[1])


def device_gaps(profile, lo: float, hi: float
                ) -> List[List[Tuple[float, float]]]:
    """Per chip that ran anything in ``[lo, hi)``, the stretches in which
    no operation ran (as ``tracereduce.reduce`` finds them)."""
    out = []
    for plane in profile.planes:
        if not tracereduce.DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if tracereduce.MODULES not in lines:
            continue
        mods = tracereduce.Events.of(lines[tracereduce.MODULES])
        if not ((mods.end > lo) & (mods.start < hi)).any():
            continue
        src = (tracereduce.Events.of(lines[tracereduce.OPS])
               if tracereduce.OPS in lines else mods)
        busy = tracereduce.union(
            tracereduce.clip(list(zip(src.start, src.end)), lo, hi))
        out.append(tracereduce.gaps(busy, lo, hi))
    return out


@dataclasses.dataclass
class SpanSummary:
    """What a traced window's program spans say."""

    seconds: Dict[str, float]                 # span_seconds in the window
    stats: Dict[str, Dict[str, int]]          # span_stats in the window
    idle_by_span: List[Tuple[str, float]]     # empty without a device plane
    idle_gaps: List[Tuple[str, float]]        # the longest pieces


def reduce(profile, top: int = 10) -> Optional[SpanSummary]:
    """The window's program spans and the chip's idle gaps cut by them;
    None when the trace holds no window span."""
    window = tracereduce.host_spans(profile, [tracereduce.WINDOW_SPAN])
    if not window:
        return None
    _, lo, hi = window[0]
    seconds = span_seconds(profile, PROGRAM_SPANS, lo, hi)
    spans = Spans.of(profile)
    pieces = [p for gaps in device_gaps(profile, lo, hi)
              for p in cut_gaps(gaps, spans)]
    return SpanSummary(seconds, span_stats(profile, PROGRAM_SPANS, lo, hi),
                       idle_by_span(pieces),
                       sorted(pieces, key=lambda t: -t[1])[:top])


def layer_metrics(seconds: Optional[Dict[str, float]],
                  counters: Optional[Dict[str, int]], n_intervals: int,
                  suffix: str = "") -> Dict[str, float]:
    """Per completed interval: the ``_ms`` metrics from span seconds, the
    ``_kb`` ones (1 KB = 1000 bytes) from the counters' change. A source
    the run lacks gives none of its metrics."""
    out: Dict[str, float] = {}
    if not n_intervals:
        return out
    if seconds is not None:
        for metric, names in SPAN_METRICS.items():
            out[metric + suffix] = 1e3 * sum(seconds[n] for n in names) \
                / n_intervals
    if counters is not None:
        for metric, name in COUNTER_METRICS.items():
            out[metric + suffix] = counters.get(name, 0) / 1e3 / n_intervals
    return out


def per_interval(seconds: Optional[Dict[str, float]],
                 counters: Optional[Dict[str, int]], n_intervals: int
                 ) -> Dict[str, float]:
    """Per completed interval: the harness ``interval`` span's ms (the sum
    of the self times and ``untraced``) and the counts of
    ``COUNTS_PER_INTERVAL``, which say what a layer's ms was spent on
    (``migrate_ms`` over ``migrated_keys``, ``plan_ms`` over ``plans``)."""
    out: Dict[str, float] = {}
    if not n_intervals:
        return out
    if seconds is not None:
        out["interval_ms"] = 1e3 * sum(seconds.values()) / n_intervals
    if counters is not None:
        for name in COUNTS_PER_INTERVAL:
            out[name] = counters.get(name, 0) / n_intervals
    return out


def run(name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, **run_cell_kw) -> Tuple[dict, dict]:
    """One run of cell ``name`` through ``core.run_cell`` (untraced, so
    its result line is that of ``--trace 0``); returns that line's object
    and this module's line: the counters' change over the window, the
    host-clock throughput, and with ``trace`` the span numbers and idle
    gaps of this module's own trace of the window."""
    import jax
    from jax.profiler import ProfileData

    seen: dict = {}
    trace_dir = tempfile.mkdtemp(prefix="span_trace_") if trace else None
    run_window = core.run_window

    def observed_window(system, pool, seconds, span):
        before = dict(COUNTERS) if COUNTERS is not None else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            span = jax.profiler.TraceAnnotation
        try:
            with span(tracereduce.WINDOW_SPAN):
                t0, t_close, intervals, error = run_window(system, pool,
                                                           seconds, span)
        finally:
            if trace:
                jax.profiler.stop_trace()
        if before is not None:
            seen["counters"] = {k: v - before.get(k, 0)
                                for k, v in COUNTERS.items()
                                if v != before.get(k, 0)}
        seen["intervals"] = intervals
        seen["window_s"] = t_close - t0
        return t0, t_close, intervals, error

    core.run_window = observed_window
    try:
        result = core.run_cell(name, seed, seconds, False, t_start=t_start,
                               **run_cell_kw)
        summary = None
        files = (sorted(Path(trace_dir).glob("**/*.xplane.pb"))
                 if trace else [])
        if files and PROGRAM_SPANS:
            summary = reduce(ProfileData.from_file(str(files[-1])))
    finally:
        core.run_window = run_window
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    done = [iv for iv in seen.get("intervals", ()) if iv.completed]
    counters = seen.get("counters")
    line: dict = {"intervals": len(done),
                  "throughput_tps": (sum(iv.tuples for iv in done)
                                     / seen["window_s"] if done else None),
                  "counters": counters}
    suffix = "." + name.rsplit(".", 1)[1] if "." in name else ""
    span_s = summary.seconds if summary is not None else None
    line["metrics"] = layer_metrics(span_s, counters, len(done), suffix)
    line["per_interval"] = per_interval(span_s, counters, len(done))
    if summary is not None:
        line["span_seconds"] = summary.seconds
        line["span_stats"] = summary.stats
        line["idle_by_span"] = [list(t) for t in summary.idle_by_span]
        line["idle_gaps"] = [list(t) for t in summary.idle_gaps]
    return result, line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    try:
        result, line = run(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    except core.Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

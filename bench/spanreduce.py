"""The program's host spans and counters in a traced run of a cell.

The program marks its host phases with spans (``repro.core.telemetry``:
``jax.profiler.TraceAnnotation``s on the ``/host:CPU`` plane, on the device
trace's clock) and counts its device transfers in ``COUNTERS``. A traced
run of ``core.run_cell`` keeps :func:`reduce` of its trace on
``Run.spans`` and the counters' change over the window on ``Run.counters``;
the per-layer readers in ``metrics/`` read them through :func:`span_ms` and
:func:`counter_kb`.

* :func:`span_seconds`: each program span's self time (its length less the
  part its child program spans cover), clipped to a window, and
  ``untraced``: the time of each harness ``interval`` span that no program
  span covers. The self times and ``untraced`` add up to the intervals'
  time. It needs no device plane;
* :func:`span_stats`: the sums of the integer stats of each span (a
  ``pull``'s ``bytes``, a ``route``'s ``table``).

The chip's idle gaps are cut at these spans by ``tracereduce.cut_gaps``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import tracereduce

INTERVAL_SPAN = "interval"
UNTRACED = "untraced"


def _clip(spans, lo: float, hi: float) -> List[tracereduce.Span]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in spans
            if e > lo and s < hi]


def span_seconds(profile, names: Sequence[str],
                 lo: float = float("-inf"), hi: float = float("inf")
                 ) -> Dict[str, float]:
    """Self seconds of each program span of ``names`` inside ``[lo, hi)``
    (ns on the trace's clock), and under ``"untraced"`` the seconds of the
    harness ``interval`` spans there that no program span covers."""
    spans = tracereduce.Spans.of(profile, names, [INTERVAL_SPAN])
    out = {n: 0.0 for n in names}
    covered: List[Tuple[float, float]] = []
    for line in spans.program:
        for name, s, e in tracereduce.segments(_clip(line, lo, hi)):
            out[name] += (e - s) * 1e-9
            covered.append((s, e))
    covered = tracereduce.union(covered)
    untraced = 0.0
    for _, s, e in _clip(spans.harness, lo, hi):
        inside = tracereduce.clip(covered, s, e)
        untraced += (e - s - sum(b - a for a, b in inside)) * 1e-9
    out[UNTRACED] = untraced
    return out


def span_stats(profile, names: Sequence[str],
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


@dataclasses.dataclass
class SpanSummary:
    """What a traced window's program spans say."""

    seconds: Dict[str, float]                 # span_seconds in the window
    stats: Dict[str, Dict[str, int]]          # span_stats in the window


def reduce(profile, names: Sequence[str]) -> Optional[SpanSummary]:
    """The self times and stats of the program spans ``names`` inside the
    window span; None when the trace holds no window span."""
    window = tracereduce.host_spans(profile, [tracereduce.WINDOW_SPAN])
    if not window:
        return None
    _, lo, hi = window[0]
    return SpanSummary(span_seconds(profile, names, lo, hi),
                       span_stats(profile, names, lo, hi))


def span_ms(run, names: Sequence[str]) -> Optional[float]:
    """The self time of the spans ``names`` per completed window interval,
    in ms; None without a traced window or where the program has no span
    of one of the names."""
    n = len(run.done())
    if run.spans is None or not n or not set(names) <= set(run.spans.seconds):
        return None
    return 1e3 * sum(run.spans.seconds[k] for k in names) / n


def counter_kb(run, name: str) -> Optional[float]:
    """The change of counter ``name`` over the window per completed
    interval, in kB (1 kB = 1000 B); None where the program has no such
    counter."""
    n = len(run.done())
    if run.counters is None or name not in run.counters or not n:
        return None
    return run.counters[name] / 1e3 / n

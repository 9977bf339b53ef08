"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

The TPU writes one plane per chip (``/device:TPU:<n>``) with a line of
programs (``XLA Modules``, one event per executed jitted program, named
``jit_<function>(<fingerprint>)``) and a line of operations (``XLA Ops``).
The harness's own host spans (``jax.profiler.TraceAnnotation``) sit on a
thread line of the ``/host:CPU`` plane, on the same clock.

The program marks its host phases with spans of its own
(``repro.core.telemetry.SPANS``) on the same plane; they nest on a thread
line.

* busy: the union of the operation intervals on a chip inside the window
  span, averaged over the chips that ran anything;
* a program's device time: the summed durations of its module events;
* idle gaps: the stretches of the window in which no operation ran, cut at
  program-span boundaries (:func:`cut_gaps`): each piece is named after the
  innermost program span open there, else after the harness span that
  overlaps it most, so a gap names the host phase that left the chip idle.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES, OPS = "XLA Modules", "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "window"


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi)`` that ``busy`` (sorted, disjoint)
    leaves."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def program_name(event_name: str) -> str:
    """``jit__interval_step_add(123)`` -> ``jit__interval_step_add``."""
    return event_name.split("(", 1)[0]


def op_name(event_name: str) -> str:
    """``%fusion.1 = s32[1025] fusion(...)`` -> ``%fusion.1``."""
    return event_name.split(" = ", 1)[0]


@dataclasses.dataclass
class Events:
    """One line's events as start/end arrays (ns) and names."""

    start: np.ndarray
    end: np.ndarray
    names: List[str]

    @classmethod
    def of(cls, line) -> "Events":
        ev = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
              for e in line.events]
        return cls(np.asarray([s for s, _, _ in ev], np.float64),
                   np.asarray([e for _, e, _ in ev], np.float64),
                   [n for _, _, n in ev])


Span = Tuple[str, float, float]                 # name, start ns, end ns


@dataclasses.dataclass
class Spans:
    """Program and harness spans of a trace's host plane, each list sorted
    by start; one program span list per thread line (spans nest there)."""

    program: List[List[Span]]
    harness: List[Span]

    @classmethod
    def of(cls, profile, program_names: Sequence[str],
           harness_names: Sequence[str]) -> "Spans":
        plane = profile.find_plane_with_name(HOST_PLANE)
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


def _harness_name(harness: Sequence[Span], starts: Sequence[float],
                  a: float, b: float) -> str:
    """The harness span overlapping ``[a, b)`` most; ``"none"`` where none
    does."""
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


@dataclasses.dataclass
class Summary:
    """What the per-layer readers take from a trace."""

    window_s: float
    busy_s: float                          # averaged over chips that ran
    programs: Dict[str, Tuple[int, float]]  # name -> (calls, device s)
    ops: List[Tuple[str, float]]           # (program:op, device s), top first
    idle_gaps: List[Tuple[str, float]]     # (host span, s) pieces, longest

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_seconds(self, name: str) -> Tuple[int, float]:
        return self.programs.get(name, (0, 0.0))


def _lines(plane) -> Dict[str, object]:
    return {line.name: line for line in plane.lines}


def host_spans(profile, names: Sequence[str]
               ) -> List[Tuple[str, float, float]]:
    """The harness's spans of ``names`` on the host plane."""
    plane = profile.find_plane_with_name(HOST_PLANE)
    if plane is None:
        return []
    wanted = set(names)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for line in plane.lines for e in line.events if e.name in wanted]


def reduce(profile, span_names: Sequence[str],
           program_names: Sequence[str] = (), top: int = 10
           ) -> Optional[Summary]:
    """Reduce a ``jax.profiler.ProfileData``; None when the trace holds no
    window span or no device plane with events in it. ``span_names`` are
    the harness's spans, ``program_names`` the program's; the ``top``
    longest idle pieces are kept."""
    window = host_spans(profile, [WINDOW_SPAN])
    if not window:
        return None
    _, lo, hi = window[0]
    spans = Spans.of(profile, program_names, span_names)
    busy_total, chips = 0.0, 0
    programs: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    idle: List[Tuple[str, float]] = []
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = _lines(plane)
        if MODULES not in lines:
            continue
        mods = Events.of(lines[MODULES])
        inside = (mods.end > lo) & (mods.start < hi)
        if not inside.any():
            continue
        chips += 1
        for i in np.nonzero(inside)[0]:
            calls = programs.setdefault(program_name(mods.names[i]), [0, 0.0])
            calls[0] += 1
            calls[1] += (mods.end[i] - mods.start[i]) * 1e-9
        busy_src = Events.of(lines[OPS]) if OPS in lines else mods
        busy = union(clip(list(zip(busy_src.start, busy_src.end)), lo, hi))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        if OPS in lines:
            order = np.argsort(mods.start)
            for s, e, n in zip(busy_src.start, busy_src.end, busy_src.names):
                if e <= lo or s >= hi:
                    continue
                j = np.searchsorted(mods.start[order], s, side="right") - 1
                prog = (program_name(mods.names[order[j]])
                        if j >= 0 and mods.end[order[j]] >= s else "?")
                key = f"{prog}:{op_name(n)}"
                ops[key] = ops.get(key, 0.0) + (e - s) * 1e-9
        idle.extend(cut_gaps(gaps(busy, lo, hi), spans))
    if not chips:
        return None
    idle.sort(key=lambda t: -t[1])
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total / chips,
        programs={k: (int(v[0]), float(v[1])) for k, v in programs.items()},
        ops=sorted(ops.items(), key=lambda t: -t[1])[:top],
        idle_gaps=idle[:top])

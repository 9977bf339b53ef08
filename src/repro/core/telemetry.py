"""Host spans and counters of the stream engine, one registry per process.

A span names one host phase of an interval: ``span(name, **args)`` is a
``jax.profiler.TraceAnnotation``, so inside a profiler session it lands on
the ``/host:CPU`` plane on the device trace's clock (integer ``args`` become
the event's stats), and outside one it costs about a microsecond. There is
no switch: spans are recorded exactly when a profiler session runs. A
process that never imported jax can have no session, so there the span is a
no-op and this module keeps the host-only paths jax-free.

Spans nest: ``pull`` may open inside ``step`` (the step's readback) or
``finish`` (the route table's deferred copy); every other span opens at
most inside the stage's entry call. What each covers:

=========  ==============================================================
span       work
=========  ==============================================================
pause      the buffered count of the pause window after a migration
route      a dense route refresh (table arrays, route, start of its host
           copy); its ``table`` stat is the padded table capacity
step       host histogram, uploads and dispatch of the fused step
pull       one device-to-host copy; its ``bytes`` stat is the array's size
finish     closed forms, outputs, emitted sum, task cost, host mirrors
stats      the per-key stats build, or the sketch ingest
trigger    the controller's imbalance test
plan       the controller's plan
migrate    the relabel of the keys a plan moved
=========  ==============================================================

``COUNTERS`` maps a name to a running total: ``d2h_bytes``/``h2d_bytes``
(every transfer the stream engine makes, through
``repro.streams.device.to_host``/``to_device``), ``route_refreshes``,
``plans``, ``migrated_keys`` (``bench/spanreduce.py`` prints each per
interval), ``d2h_copies`` (one per device-to-host copy, whether or not it
was started ahead; ``bench/spanreduce.py``'s ``counters`` hold its change
over the window), ``paused_tuples`` (the tuples a device backend's pause
window buffers, its reports' ``buffered`` summed), and
``retrace.<module>.<function>`` (incremented while a
jitted function is traced, so a test can see that it compiled once).
"""

from __future__ import annotations

import contextlib
import sys
from typing import Dict

SPANS = ("pause", "route", "step", "pull", "finish", "stats", "trigger",
         "plan", "migrate")

COUNTERS: Dict[str, int] = {}


def count(name: str, n: int = 1) -> None:
    COUNTERS[name] = COUNTERS.get(name, 0) + int(n)


def span(name: str, **args):
    """A context manager that marks one host phase in a profiler trace."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:         # no jax, so no profiler session to record to
        return contextlib.nullcontext()
    return profiler.TraceAnnotation(name, **args)

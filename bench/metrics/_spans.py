"""The program's own host spans in a traced window, selected by exact name.

The program writes them with ``jax.profiler.TraceAnnotation`` (``serve.*``,
``sim.*``, ``train.*``), so they sit on the profiler's one clock beside the
device's events.  ``Trace.spans`` matches by prefix, and ``serve.profile``
is a prefix of its own children, so the readers select by name here.
A program without the span gives an empty list, and its readers None.
"""
from __future__ import annotations

import bisect
from typing import List, Sequence

from bench.tracing import Event


def named(trace, name: str) -> List[Event]:
    """Host events called exactly ``name``, in time order."""
    return sorted((e for e in trace.host if e.name == name),
                  key=lambda e: e.start)


def in_window(ctx, name: str) -> List[Event]:
    """The ``name`` spans that share some time with the driver's traced
    window (for the serve driver, the spans of the timed steps)."""
    window = ctx.driver_window(ctx.trace)
    if window is None:
        return []
    lo, hi = window
    return [s for s in named(ctx.trace, name) if s.end > lo and s.start < hi]


def count_starting_inside(events: Sequence[Event],
                          spans: Sequence[Event]) -> int:
    """How many ``events`` start inside one of ``spans`` (spans disjoint)."""
    starts = sorted(e.start for e in events)
    return sum(bisect.bisect_left(starts, s.end)
               - bisect.bisect_left(starts, s.start) for s in spans)


def ms_per_call(ctx, name: str):
    """Mean over the driver's traced calls (its ``bench.*.call`` spans) of
    the time, in ms, of the ``name`` spans that start inside each."""
    calls = ctx.trace.spans(ctx.driver.span_name)
    inner = named(ctx.trace, name)
    if not calls or not inner:
        return None
    ns = sum(e.duration for c in calls for e in inner
             if c.start <= e.start < c.end)
    return 1e-6 * ns / len(calls)

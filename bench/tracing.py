"""Reduce a JAX profiler trace to device busy time, program time and idle gaps.

The profiler writes an ``.xplane.pb`` file.  :func:`load` turns it into a
:class:`Trace` of plain intervals, and everything else here works on those
intervals only, so the reduction is tested on synthetic traces with known
answers (``bench/tests/test_tracing.py``).

Where the intervals come from:

* device planes (``/device:...``): the ``XLA Ops`` line holds one event per
  operation run on the device, the ``XLA Modules`` line one event per
  execution of a compiled program (named after the jitted function);
* host planes (``/host:...``): every line's events, among them the spans the
  benchmark writes with ``jax.profiler.TraceAnnotation`` (named ``bench.*``).

Times are nanoseconds on the profiler's one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float   # ns
    end: float     # ns

    @property
    def duration(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class DeviceLines:
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, DeviceLines]   # device plane name -> its lines
    host: List[Event]                 # every host event, all threads

    def spans(self, prefix: str = "bench.") -> List[Event]:
        """The benchmark's own host spans, in time order."""
        return sorted((e for e in self.host if e.name.startswith(prefix)),
                      key=lambda e: e.start)

    def modules(self, substring: str) -> List[Event]:
        """Executions of programs whose name holds ``substring``, on every
        device, in time order."""
        return sorted((e for d in self.devices.values() for e in d.modules
                       if substring in e.name), key=lambda e: e.start)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file into a :class:`Trace`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, DeviceLines] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
            mods = (_events(lines[MODULES_LINE]) if MODULES_LINE in lines
                    else [])
            if ops or mods:
                devices[plane.name] = DeviceLines(ops, mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    return Trace(devices=devices, host=host)


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        start = float(e.start_ns)
        out.append(Event(e.name, start, start + float(e.duration_ns)))
    return out


# --------------------------------------------------------------------- #
# interval arithmetic
# --------------------------------------------------------------------- #
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals; sorted, disjoint."""
    merged: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    out = []
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b))
    return out


def covered(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` covered by the union of ``intervals``."""
    return sum(b - a for a, b in clip(union(intervals), lo, hi))


def gaps(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of ``[lo, hi]`` that no interval covers."""
    out, cursor = [], lo
    for a, b in clip(union(intervals), lo, hi):
        if a > cursor:
            out.append((cursor, a))
        cursor = max(cursor, b)
    if hi > cursor:
        out.append((cursor, hi))
    return out


# --------------------------------------------------------------------- #
# the reductions the metric readers use
# --------------------------------------------------------------------- #
def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi]`` in which an operation ran, averaged over
    the devices that ran any operation in it."""
    per_device = [covered(((e.start, e.end) for e in d.ops), lo, hi)
                  for d in trace.devices.values()]
    per_device = [b for b in per_device if b > 0]
    return sum(per_device) / len(per_device) if per_device else 0.0


def idle_share(trace: Trace, lo: float, hi: float) -> Optional[float]:
    """1 - busy / window over ``[lo, hi]``; None for an empty window or a
    window in which no device ran anything."""
    busy = busy_ns(trace, lo, hi)
    if hi <= lo or busy <= 0:
        return None
    return 1.0 - busy / (hi - lo)


def op_name(text: str) -> str:
    """An operation's short name: the HLO instruction's name, without its
    shapes and operands (``%fusion.12 = bf16[...] fusion(...)`` ->
    ``%fusion.12``)."""
    return text.split(" = ", 1)[0]


def self_times(events: Sequence[Event], lo: float, hi: float
               ) -> Dict[str, float]:
    """Nanoseconds inside ``[lo, hi]`` of each operation's own time.

    Operations on one line nest (a ``while`` holds its body's operations):
    each event's time less the time of the events it directly holds.
    """
    totals: Dict[str, float] = {}
    stack: List[List] = []          # [event, clipped ns held by children]

    def close(entry):
        e, children = entry
        own = min(e.end, hi) - max(e.start, lo)
        if own > 0:
            name = op_name(e.name)
            totals[name] = totals.get(name, 0.0) + max(0.0, own - children)
        if stack:
            stack[-1][1] += max(0.0, own)

    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            close(stack.pop())
        stack.append([e, 0.0])
    while stack:
        close(stack.pop())
    return totals


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10
            ) -> List[Tuple[str, float]]:
    """Device operations by own seconds inside ``[lo, hi]`` (summed over
    devices, divided by their count), the largest ``n``."""
    totals: Dict[str, float] = {}
    n_dev = max(1, len(trace.devices))
    for d in trace.devices.values():
        for name, ns in self_times(d.ops, lo, hi).items():
            totals[name] = totals.get(name, 0.0) + ns / n_dev
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns * 1e-9) for name, ns in ranked]


def labels_at(host: Sequence[Event], times: Sequence[float]) -> List[str]:
    """For each time, the name of the innermost (shortest) host event that
    holds it, or ``"none"``; one sweep over the events sorted by start."""
    order = sorted(range(len(times)), key=lambda i: times[i])
    events = sorted(host, key=lambda e: e.start)
    out = ["none"] * len(times)
    active: List[Tuple[float, float, str]] = []   # heap of (end, dur, name)
    k = 0
    for i in order:
        t = times[i]
        while k < len(events) and events[k].start <= t:
            e = events[k]
            heapq.heappush(active, (e.end, e.duration, e.name))
            k += 1
        while active and active[0][0] < t:
            heapq.heappop(active)
        if active:
            out[i] = min(active, key=lambda a: a[1])[2]
    return out


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10
              ) -> List[Tuple[str, float]]:
    """Idle time inside ``[lo, hi]`` summed by what the host was doing at
    the middle of each gap (the innermost host event there), the largest
    ``n`` labels.  Gaps are those of the first device that ran anything."""
    for d in trace.devices.values():
        if d.ops:
            ops = [(e.start, e.end) for e in d.ops]
            break
    else:
        return []
    idle = gaps(ops, lo, hi)
    names = labels_at(trace.host, [(a + b) / 2 for a, b in idle])
    totals: Dict[str, float] = {}
    for (a, b), name in zip(idle, names):
        totals[name] = totals.get(name, 0.0) + (b - a)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns * 1e-9) for name, ns in ranked]


def span_window(trace: Trace, prefix: str = "bench.") -> Optional[Interval]:
    """From the start of the first benchmark span to the end of the last."""
    spans = trace.spans(prefix)
    if not spans:
        return None
    return spans[0].start, max(s.end for s in spans)

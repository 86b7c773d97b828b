"""Capture glue: run the streaming simulator with trace attached.

Thin wrappers over :mod:`repro.rinn.batchsim`'s traced entry points that
return :class:`~repro.trace.store.TraceStore` objects (plus the usual
:class:`~repro.rinn.streamsim.SimResult`), with a calibration pass that
picks a window stride matched to the run's actual length.

The calibration run is cheap by construction: fault plans, capacities and
the profiled flag are runtime arguments of the shape-bucketed executable
(PR 7), so it reuses the cached program — one extra launch, no extra
compile.  The traced executable itself is cached per ``windows`` value.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

from repro.rinn.batchsim import run_sim, run_sim_traced, run_sim_traced_batch
from repro.rinn.streamsim import CompiledSim, FaultPlan, SimResult

from .store import Edge, TraceStore


def _calibrated_stride(sim: CompiledSim, windows: int, max_cycles: int,
                       profiled, faults, capacity_overrides) -> int:
    probe = run_sim(
        sim, profiled=bool(profiled) if not isinstance(profiled, (list, tuple))
        else any(profiled),
        max_cycles=max_cycles, faults=faults,
        capacity_overrides=capacity_overrides)
    return max(1, math.ceil(max(probe.cycles, 1) / windows))


def trace_run(
    sim: CompiledSim, *, profiled: bool = False, max_cycles: int = 200_000,
    faults: Optional[FaultPlan] = None,
    capacity_overrides: Optional[Dict[Edge, int]] = None,
    windows: int = 256, stride: Optional[int] = None, calibrate: bool = True,
) -> Tuple[SimResult, TraceStore]:
    """One traced run -> (result, store).

    ``calibrate=True`` (default) first replays the run untraced to learn
    its cycle count and sets ``stride = ceil(cycles / windows)``, so short
    runs get fine-grained timelines instead of collapsing into one window.
    Pass an explicit ``stride`` (or ``calibrate=False``) to skip it.
    """
    if stride is None and calibrate:
        stride = _calibrated_stride(sim, windows, max_cycles, profiled,
                                    faults, capacity_overrides)
    res, buffers = run_sim_traced(
        sim, profiled=profiled, max_cycles=max_cycles, faults=faults,
        capacity_overrides=capacity_overrides, windows=windows,
        stride=stride)
    return res, TraceStore.from_sim(sim, res, buffers)


def trace_pair(
    sim: CompiledSim, *, max_cycles: int = 200_000,
    faults: Optional[FaultPlan] = None,
    capacity_overrides: Optional[Dict[Edge, int]] = None,
    windows: int = 256, stride: Optional[int] = None, calibrate: bool = True,
) -> Tuple[Tuple[SimResult, TraceStore], Tuple[SimResult, TraceStore]]:
    """The cosim pair (unprofiled, profiled) traced as one vmapped batch.

    Both lanes share one stride so the two timelines are window-aligned —
    exactly what :func:`repro.trace.diff.diff_traces` wants.
    """
    if stride is None and calibrate:
        stride = _calibrated_stride(sim, windows, max_cycles, True,
                                    faults, capacity_overrides)
    pairs = run_sim_traced_batch(
        sim, plans=[faults, faults], profiled=[False, True],
        capacity_overrides=[capacity_overrides, capacity_overrides],
        max_cycles=max_cycles, windows=windows, stride=stride)
    return tuple((res, TraceStore.from_sim(sim, res, buffers))
                 for res, buffers in pairs)  # type: ignore[return-value]


def trace_lanes(
    sim: CompiledSim, plans: List[Optional[FaultPlan]], *,
    profiled: bool = False, max_cycles: int = 200_000,
    windows: int = 256, stride: Optional[int] = None,
) -> List[Tuple[SimResult, TraceStore]]:
    """A traced fault campaign: one store per fault lane, shared stride."""
    if stride is None:
        stride = _calibrated_stride(sim, windows, max_cycles, profiled,
                                    plans[0] if plans else None, None)
    pairs = run_sim_traced_batch(
        sim, plans=plans, profiled=profiled, max_cycles=max_cycles,
        windows=windows, stride=stride)
    return [(res, TraceStore.from_sim(sim, res, buffers))
            for res, buffers in pairs]

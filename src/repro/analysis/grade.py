"""Grading static predictions against observed traces.

The static dataflow pass (:mod:`repro.analysis.dataflow`) predicts which
FIFOs saturate under a given capacity config.  This module scores those
predictions against a :class:`repro.trace.TraceStore` of the actual run —
per-edge confusion outcomes plus precision/recall — closing the
cross-validation loop the paper's methodology demands: a static model is
only trustworthy if its saturation set matches the profiled one.

Mispredictions are *localized* on the trace's time axis: false negatives
point at the windows where saturation actually happened; with a baseline
trace supplied, both kinds of misprediction also carry the windows where
the observed run diverged from baseline
(:func:`repro.trace.diff_traces` ``window_level=True``), so a wrong
prediction comes with the when, not just the which.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.rinn.batchsim import run_sim
from repro.trace import TraceStore, diff_traces, edge_name

from .dataflow import StaticAnalysis

Edge = Tuple[str, str]


@dataclasses.dataclass(frozen=True)
class EdgeOutcome:
    """One edge's predicted-vs-observed saturation verdict."""

    edge: Edge
    predicted: bool           # static: peak backlog reaches capacity
    observed: bool            # trace: any sample at capacity (full_frac > 0)
    capacity: int
    static_peak: int          # predicted peak backlog
    observed_peak: float      # traced peak occupancy
    windows: Tuple[int, ...] = ()   # localization of the evidence

    @property
    def correct(self) -> bool:
        return self.predicted == self.observed

    @property
    def kind(self) -> str:
        if self.predicted and self.observed:
            return "TP"
        if self.predicted:
            return "FP"
        return "FN" if self.observed else "TN"


@dataclasses.dataclass
class PredictionGrade:
    """Confusion summary of one static-vs-trace comparison."""

    outcomes: List[EdgeOutcome]

    def _kind(self, k: str) -> List[EdgeOutcome]:
        return [o for o in self.outcomes if o.kind == k]

    @property
    def true_pos(self) -> List[EdgeOutcome]:
        return self._kind("TP")

    @property
    def false_pos(self) -> List[EdgeOutcome]:
        return self._kind("FP")

    @property
    def false_neg(self) -> List[EdgeOutcome]:
        return self._kind("FN")

    @property
    def precision(self) -> float:
        """Of the edges predicted saturated, the fraction that were.
        1.0 (vacuous) when nothing was predicted."""
        predicted = [o for o in self.outcomes if o.predicted]
        if not predicted:
            return 1.0
        return len(self.true_pos) / len(predicted)

    @property
    def recall(self) -> float:
        observed = [o for o in self.outcomes if o.observed]
        if not observed:
            return 1.0
        return len(self.true_pos) / len(observed)

    def summary(self) -> str:
        lines = [f"# saturation grade — {len(self.outcomes)} edge(s): "
                 f"{len(self.true_pos)} TP / {len(self.false_pos)} FP / "
                 f"{len(self.false_neg)} FN; "
                 f"precision {self.precision:.2f} recall {self.recall:.2f}"]
        for o in self.outcomes:
            if o.correct and not o.observed:
                continue
            where = ""
            if o.windows:
                lo, hi = o.windows[0], o.windows[-1]
                where = (f"  @ w{lo}" if lo == hi else f"  @ w{lo}-{hi}")
            lines.append(
                f"  {o.kind} {edge_name(o.edge):34s} "
                f"static {o.static_peak}/{o.capacity} "
                f"observed peak {o.observed_peak:g}{where}")
        return "\n".join(lines)

    def __str__(self) -> str:
        return self.summary()


def grade_saturation(
    analysis: StaticAnalysis, store: TraceStore, *,
    capacities: Dict[Edge, int],
    baseline: Optional[TraceStore] = None,
) -> PredictionGrade:
    """Score static saturation predictions against one observed trace.

    ``capacities`` must be the config the traced run actually used (see
    :func:`repro.analysis.dataflow.effective_capacities`).  Only edges
    present in the trace are graded — the static model cannot be judged on
    channels nobody observed.  With ``baseline``, mispredicted edges carry
    the diverging-window span from the baseline diff; false negatives
    always carry the windows where the trace shows time-at-full.
    """
    predicted = {b.edge for b in analysis.predicted_saturated(capacities)}
    stats = store.stats_by_name()
    diff_windows: Dict[str, Tuple[int, ...]] = {}
    if baseline is not None:
        for d in diff_traces(baseline, store, window_level=True).deltas:
            diff_windows[d.name] = d.windows or ()

    outcomes: List[EdgeOutcome] = []
    for e, b in sorted(analysis.bounds.items()):
        name = edge_name(e)
        st = stats.get(name)
        if st is None or st.samples == 0:
            continue
        observed = st.full_frac > 0.0
        windows: Tuple[int, ...] = ()
        if observed and not (e in predicted):
            full = store.timeline(name)["full_cycles"]
            windows = tuple(int(w) for w in np.flatnonzero(full > 0))
        elif (e in predicted) != observed:
            windows = diff_windows.get(name, ())
        outcomes.append(EdgeOutcome(
            edge=e, predicted=e in predicted, observed=observed,
            capacity=int(capacities.get(e, 0)),
            static_peak=b.peak_backlog, observed_peak=st.peak,
            windows=windows))
    return PredictionGrade(outcomes=outcomes)


# --------------------------------------------------------------------- #
# decidability: how much of the capacity lattice gets a verdict
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class DecisionOutcome:
    """One capacity map's verdict, and whether the simulator agrees."""

    label: str
    verdict: str              # "safe" | "deadlock" (never "unknown")
    method: str               # how the checker decided
    completion_cycle: Optional[int]
    confirmed: Optional[bool]  # None when ground truth was not run

    @property
    def decided(self) -> bool:
        return self.verdict in ("safe", "deadlock")


@dataclasses.dataclass
class DecisionGrade:
    """Decided-fraction metric over a family of capacity maps.

    Before the model checker this fraction measured how much of the
    capacity lattice the static layer could call; with the total decision
    procedure it is pinned at 1.0 and the interesting number becomes
    ``confirmed_fraction`` — how many verdicts the simulator corroborates.
    """

    outcomes: List[DecisionOutcome]

    @property
    def decided_fraction(self) -> float:
        if not self.outcomes:
            return 1.0
        return sum(o.decided for o in self.outcomes) / len(self.outcomes)

    @property
    def undecided(self) -> List[DecisionOutcome]:
        return [o for o in self.outcomes if not o.decided]

    @property
    def confirmed_fraction(self) -> float:
        checked = [o for o in self.outcomes if o.confirmed is not None]
        if not checked:
            return 1.0
        return sum(bool(o.confirmed) for o in checked) / len(checked)

    @property
    def misdecided(self) -> List[DecisionOutcome]:
        return [o for o in self.outcomes if o.confirmed is False]

    def summary(self) -> str:
        n = len(self.outcomes)
        safe = sum(o.verdict == "safe" for o in self.outcomes)
        lines = [f"# decidability grade — {n} map(s): {safe} safe / "
                 f"{n - safe - len(self.undecided)} deadlock / "
                 f"{len(self.undecided)} undecided; decided "
                 f"{self.decided_fraction:.2f}, confirmed "
                 f"{self.confirmed_fraction:.2f}"]
        for o in self.misdecided + self.undecided:
            lines.append(f"  !! {o.label}: {o.verdict} ({o.method}) "
                         f"confirmed={o.confirmed}")
        return "\n".join(lines)

    def __str__(self) -> str:
        return self.summary()


def grade_decidability(
    analysis: StaticAnalysis,
    capacity_maps: Dict[str, Dict[Edge, int]], *,
    profiled: bool = False, confirm: bool = False,
    max_cycles: int = 200_000,
) -> DecisionGrade:
    """Run the total decision procedure over a labelled family of maps.

    With ``confirm=True`` every verdict is checked against ``run_sim``
    ground truth: a ``safe`` verdict must complete at exactly its predicted
    cycle, a ``deadlock`` certificate must replay to the certified stall
    (:meth:`~repro.analysis.modelcheck.DeadlockCertificate.confirm`).
    """
    outcomes: List[DecisionOutcome] = []
    for label, caps in capacity_maps.items():
        res = analysis.check(caps, profiled=profiled)
        confirmed: Optional[bool] = None
        if confirm:
            if res.safe:
                sim_res = run_sim(analysis.sim, profiled=profiled,
                                  max_cycles=max_cycles,
                                  capacity_overrides=dict(caps))
                confirmed = (sim_res.completed
                             and sim_res.cycles == res.completion_cycle)
            else:
                confirmed = res.certificate.confirm(analysis.sim)
        outcomes.append(DecisionOutcome(
            label=label, verdict=res.verdict, method=res.method,
            completion_cycle=res.completion_cycle, confirmed=confirmed))
    return DecisionGrade(outcomes=outcomes)

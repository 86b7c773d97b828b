"""Bounded-capacity model checking of the streaming machine — total verdicts.

The PR 9 dataflow layer (:mod:`repro.analysis.dataflow`) decides deadlock
freedom only at the extremes: ``safe`` when every capacity meets its
schedule-preserving bound (the replay argument) and ``deadlock`` when a
fork/merge cut is provably starved before its first firing.  Everything in
between was ``unknown`` — exactly the band ROADMAP asked us to close.

This module closes it with an **exact bounded-capacity replay**: a pure
NumPy re-execution of the simulator's blocking semantics (the same
per-cycle enable conditions as :func:`repro.rinn.batchsim._simulate`, with
capacities as the only fault channel) that terminates on *every* input —
the machine's counters are monotone, so it either completes or reaches a
no-progress fixpoint in a provably bounded number of steps.  No JAX trace,
no XLA compile, no heuristic idle limit: idle gaps are jumped analytically
(the only state that changes in a fire-free cycle is timers), and a
deadlock is declared exactly when no fire is enabled and no timer is
pending — a true fixpoint, not a timeout.

Three results come out of it:

* :func:`check_capacities` — a **total** two-valued decision procedure:
  every capacity map gets ``safe`` (with the exact completion cycle) or
  ``deadlock`` (with a structured, replayable
  :class:`DeadlockCertificate`), never ``unknown``;
* :class:`DeadlockCertificate` — the cycle in the blocked-waits-for graph
  at the stall fixpoint (who waits on whom, through which FIFO, at what
  occupancy), plus enough state to confirm the stall against ``run_sim``
  (:meth:`DeadlockCertificate.confirm`);
* :func:`minimize_capacities` — per-edge binary search between 1 and the
  PR 9 schedule-preserving bound, harvesting peak occupancies from every
  safe replay to shrink sibling edges for free, emitting an
  :class:`ExactSizingPlan` that is provably Pareto-minimal: lowering any
  single edge of the plan by one word deadlocks the machine.

Soundness leans on one standard monotonicity fact about blocking dataflow
machines (and the property tests check it empirically against ``run_sim``):
growing any FIFO never delays any event, so *safety is upward closed* in
the capacity lattice — if a map completes, every pointwise-larger map
completes, and if a map deadlocks, every pointwise-smaller map deadlocks.
Upward closure is what makes the per-edge binary search valid, keeps
deadlock witnesses valid as sibling capacities shrink, and turns the final
map of :func:`minimize_capacities` into a Pareto-minimality proof.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.rinn.batchsim import run_sim
from repro.rinn.streamsim import CompiledSim, FaultPlan

Edge = Tuple[str, str]

VERDICT_SAFE = "safe"
VERDICT_DEADLOCK = "deadlock"

_BIG_CAP = np.int64(1) << 60

WAIT_FULL = "full"      # producer waits for the consumer to pop
WAIT_EMPTY = "empty"    # consumer waits for the producer to push


# --------------------------------------------------------------------- #
# packed machine + exact replay
# --------------------------------------------------------------------- #
class _Packed:
    """The compiled machine lowered to int64 NumPy, reusable across probes."""

    __slots__ = (
        "sim", "n", "e", "in_edges", "out_edges", "total_in", "total_out",
        "fill", "ii", "extra_lat", "is_src", "rate_eq", "safe_in",
        "prof_node", "any_prof", "pf_period", "pf_stall", "source_ii",
        "total_events", "max_steps", "idle_bound", "profiled",
    )

    def __init__(self, sim: CompiledSim, profiled: bool):
        self.sim = sim
        self.profiled = bool(profiled)
        self.n = len(sim.node_ids)
        self.e = len(sim.edge_list)
        self.in_edges = sim.in_edges.astype(np.int64)
        self.out_edges = sim.out_edges.astype(np.int64)
        self.total_in = sim.total_in.astype(np.int64)
        self.total_out = sim.total_out.astype(np.int64)
        self.fill = sim.fill.astype(np.int64)
        self.ii = sim.ii.astype(np.int64)
        self.extra_lat = sim.extra_lat.astype(np.int64)
        self.is_src = sim.is_source.astype(bool)
        self.rate_eq = self.total_in == self.total_out
        self.safe_in = np.maximum(self.total_in, 1)
        self.prof_node = sim.profiled.astype(bool) & self.profiled
        self.any_prof = bool(self.prof_node.any())
        self.pf_period = max(1, int(sim.pf_period))
        self.pf_stall = int(sim.pf_stall)
        self.source_ii = int(sim.source_ii)
        self.total_events = int(self.total_in.sum() + self.total_out.sum())
        # every iteration fires >= 1 event or jumps a timer to zero; timers
        # only re-arm on fires, so <= 3N+2 fire-free iterations per fire
        self.max_steps = (self.total_events + 2) * (3 * self.n + 4) + 64
        # the simulator's own longest legitimate quiet period (batchsim)
        self.idle_bound = int(
            2 * (int(sim.ii.max(initial=1)) + sim.source_ii + sim.pf_stall)
            + int(sim.extra_lat.max(initial=0)) + 16)


def _cap_array(p: _Packed, capacities: Dict[Edge, int]) -> np.ndarray:
    cap = np.full(p.e + 1, _BIG_CAP, np.int64)
    for k, e in enumerate(p.sim.edge_list):
        cap[k] = int(capacities.get(e, p.sim.capacity))
    return cap


@dataclasses.dataclass
class ReplayOutcome:
    """Raw result of one exact bounded replay (internal currency)."""

    completed: bool
    cycles: int                # completion cycle, or the stall fixpoint
    last_fire_cycle: int       # cycle index of the last event (-1: none)
    fifo: np.ndarray           # [E] end-state occupancies
    peak: np.ndarray           # [E] max end-of-cycle occupancy seen
    consumed: np.ndarray       # [N]
    produced: np.ndarray       # [N]


def bounded_replay(sim: CompiledSim, capacities: Dict[Edge, int], *,
                   profiled: bool = False,
                   _packed: Optional[_Packed] = None) -> ReplayOutcome:
    """Execute the machine's exact blocking semantics under ``capacities``.

    Always terminates: per-cycle enable conditions are re-evaluated with
    the same dataflow as the jitted simulator, fire-free gaps are jumped by
    the minimum pending timer, and a state where nothing fires and no
    timer is pending is a permanent fixpoint (the machine is deterministic
    and fire-free cycles change nothing but timers).  Completion cycles are
    bit-identical to :func:`repro.rinn.batchsim.run_sim`.
    """
    p = _packed if _packed is not None else _Packed(sim, profiled)
    cap = _cap_array(p, capacities)

    fifo = np.zeros(p.e + 1, np.int64)
    fifo[p.e] = 1                      # dummy slot: always readable, never full
    peak = np.zeros(p.e + 1, np.int64)
    consumed = np.zeros(p.n, np.int64)
    produced = np.zeros(p.n, np.int64)
    ii_t = np.zeros(p.n, np.int64)
    drain_t = p.extra_lat.copy()
    src_t = np.zeros(p.n, np.int64)
    cyc = 0
    last_fire = -1

    for _ in range(p.max_steps):
        if bool((produced >= p.total_out).all()):
            return ReplayOutcome(True, cyc, last_fire, fifo[:p.e].copy(),
                                 peak[:p.e].copy(), consumed, produced)
        in_counts = fifo[p.in_edges]
        in_avail = (in_counts >= 1).all(axis=1)
        consume = (in_avail & (ii_t == 0) & (consumed < p.total_in)
                   & ~p.is_src)
        consumed_next = consumed + consume
        done_in = consumed_next >= p.total_in
        prog = np.maximum(consumed_next - p.fill, 0)
        rate_allowed = np.where(p.rate_eq, prog,
                                (prog * p.total_out) // p.safe_in)
        allowed = np.where(done_in | p.is_src, p.total_out,
                           np.clip(rate_allowed, 0, p.total_out))
        out_space = (fifo[p.out_edges] < cap[p.out_edges]).all(axis=1)
        src_ok = ~p.is_src | (src_t == 0)
        produce = ((produced < allowed) & out_space & src_ok
                   & (drain_t == 0) & (produced < p.total_out))

        if bool(consume.any()) or bool(produce.any()):
            fifo += (np.bincount(p.out_edges[produce].ravel(),
                                 minlength=p.e + 1)
                     - np.bincount(p.in_edges[consume].ravel(),
                                   minlength=p.e + 1))
            fifo[p.e] = 1
            np.maximum(peak, fifo, out=peak)
            produced = produced + produce
            if p.any_prof:
                stall = np.where(
                    p.prof_node & consume
                    & (consumed_next % p.pf_period == 0), p.pf_stall, 0)
                ii_t = np.where(consume, p.ii - 1 + stall,
                                np.maximum(ii_t - 1, 0))
            else:
                ii_t = np.where(consume, p.ii - 1, np.maximum(ii_t - 1, 0))
            drain_t = np.where(done_in & (drain_t > 0), drain_t - 1, drain_t)
            src_t = np.where(p.is_src & produce, p.source_ii - 1,
                             np.maximum(src_t - 1, 0))
            consumed = consumed_next
            cyc += 1
            last_fire = cyc
            continue

        # fire-free cycle: only timers move.  Jump to the next expiry; with
        # no pending timer the state is a permanent fixpoint (deadlock).
        pending = [int(ii_t[ii_t > 0].min()) if (ii_t > 0).any() else 0,
                   int(src_t[src_t > 0].min()) if (src_t > 0).any() else 0]
        dr = drain_t[done_in & (drain_t > 0)]
        if dr.size:
            pending.append(int(dr.min()))
        pending = [t for t in pending if t > 0]
        if not pending:
            return ReplayOutcome(False, cyc, last_fire, fifo[:p.e].copy(),
                                 peak[:p.e].copy(), consumed, produced)
        dt = min(pending)
        cyc += dt
        ii_t = np.maximum(ii_t - dt, 0)
        src_t = np.maximum(src_t - dt, 0)
        drain_t = np.where(done_in, np.maximum(drain_t - dt, 0), drain_t)

    raise RuntimeError(
        "bounded replay exceeded its provable step bound "
        f"({p.max_steps} steps) — machine invariants violated")


# --------------------------------------------------------------------- #
# deadlock certificates
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class WaitFor:
    """One edge of the blocked-waits-for graph at the stall fixpoint."""

    actor: str
    waits_on: str
    kind: str                  # WAIT_FULL | WAIT_EMPTY
    fifo: Edge
    occupancy: int
    capacity: int

    def __str__(self) -> str:
        return (f"{self.actor} -[{self.kind} {'->'.join(self.fifo)} "
                f"{self.occupancy}/{self.capacity}]-> {self.waits_on}")

    def to_dict(self) -> Dict:
        return {"actor": self.actor, "waits_on": self.waits_on,
                "kind": self.kind, "fifo": "->".join(self.fifo),
                "occupancy": self.occupancy, "capacity": self.capacity}


@dataclasses.dataclass
class DeadlockCertificate:
    """A replayable witness that a capacity map deadlocks the machine.

    ``cycle`` is a cycle in the blocked-waits-for graph at the fixpoint:
    each element says which actor is stuck waiting on which neighbour,
    through which FIFO, and at what occupancy.  Such a cycle always exists
    at a fixpoint — every unfinished actor is blocked on a full out-edge
    (backpressure) or an empty in-edge (starvation), and both kinds of wait
    point at another blocked actor.  ``confirm`` replays the same capacity
    map through the real simulator and checks that it stalls in exactly
    this state.
    """

    stall_cycle: int                 # first cycle of the permanent fixpoint
    last_fire_cycle: int             # last cycle any actor fired
    cycle: List[WaitFor]             # the blocking cycle (the proof core)
    waits: List[WaitFor]             # every wait edge at the fixpoint
    occupancies: Dict[Edge, int]     # all FIFO occupancies at the fixpoint
    capacities: Dict[Edge, int]      # the capacity map that was checked
    consumed: Dict[str, int]
    produced: Dict[str, int]
    profiled: bool
    replay_max_cycles: int           # enough for run_sim to hit the stall

    @property
    def blocked_edges(self) -> List[Edge]:
        return sorted({w.fifo for w in self.waits})

    def cycle_str(self) -> str:
        if not self.cycle:
            return "<no cycle>"
        hops = [f"{w.actor} -[{w.kind} {w.occupancy}/{w.capacity}]->"
                for w in self.cycle]
        return " ".join(hops) + f" {self.cycle[0].actor}"

    def confirm(self, sim: CompiledSim) -> bool:
        """Replay the prefix through ``run_sim`` and check it stalls in the
        certified state (same occupancies, same per-actor progress)."""
        res = run_sim(sim, profiled=self.profiled,
                      max_cycles=self.replay_max_cycles,
                      capacity_overrides=dict(self.capacities))
        if res.completed or not res.deadlocked:
            return False
        if any(res.fifo_final.get(e) != occ
               for e, occ in self.occupancies.items()):
            return False
        return (res.node_consumed == self.consumed
                and res.node_produced == self.produced)

    def summary(self) -> str:
        lines = [f"deadlock certificate: fixpoint at cycle "
                 f"{self.stall_cycle} (last fire at "
                 f"{self.last_fire_cycle}); blocking cycle: "
                 f"{self.cycle_str()}"]
        for w in self.waits:
            lines.append(f"  {w}")
        return "\n".join(lines)

    def to_dict(self) -> Dict:
        return {
            "stall_cycle": self.stall_cycle,
            "last_fire_cycle": self.last_fire_cycle,
            "cycle": [w.to_dict() for w in self.cycle],
            "waits": [w.to_dict() for w in self.waits],
            "occupancies": {"->".join(e): o
                            for e, o in sorted(self.occupancies.items())},
            "capacities": {"->".join(e): c
                           for e, c in sorted(self.capacities.items())},
            "consumed": dict(self.consumed),
            "produced": dict(self.produced),
            "profiled": self.profiled,
            "replay_max_cycles": self.replay_max_cycles,
        }

    def __str__(self) -> str:
        return self.summary()


def _build_certificate(p: _Packed, cap: Dict[Edge, int],
                       out: ReplayOutcome) -> DeadlockCertificate:
    sim = p.sim
    node_of = {nid: i for i, nid in enumerate(sim.node_ids)}
    eidx = {e: k for k, e in enumerate(sim.edge_list)}
    in_of: Dict[str, List[Edge]] = {n: [] for n in sim.node_ids}
    out_of: Dict[str, List[Edge]] = {n: [] for n in sim.node_ids}
    for e in sim.edge_list:
        out_of[e[0]].append(e)
        in_of[e[1]].append(e)

    waits: List[WaitFor] = []
    next_of: Dict[str, List[WaitFor]] = {}
    for nid in sim.node_ids:
        i = node_of[nid]
        mine: List[WaitFor] = []
        if (out.consumed[i] < p.total_in[i]) and not p.is_src[i]:
            for e in in_of[nid]:
                if out.fifo[eidx[e]] == 0:
                    mine.append(WaitFor(actor=nid, waits_on=e[0],
                                        kind=WAIT_EMPTY, fifo=e, occupancy=0,
                                        capacity=int(cap[e])))
        if out.produced[i] < p.total_out[i]:
            for e in out_of[nid]:
                occ = int(out.fifo[eidx[e]])
                if occ >= cap[e]:
                    mine.append(WaitFor(actor=nid, waits_on=e[1],
                                        kind=WAIT_FULL, fifo=e,
                                        occupancy=occ, capacity=int(cap[e])))
        if mine:
            next_of[nid] = mine
            waits.extend(mine)

    # walk the waits-for graph until a node repeats; the tail is the cycle
    cycle: List[WaitFor] = []
    if next_of:
        path: List[WaitFor] = []
        seen_at: Dict[str, int] = {}
        node = next(iter(next_of))
        while node in next_of and node not in seen_at:
            seen_at[node] = len(path)
            step = next_of[node][0]
            path.append(step)
            node = step.waits_on
        if node in seen_at:
            cycle = path[seen_at[node]:]

    return DeadlockCertificate(
        stall_cycle=out.cycles, last_fire_cycle=out.last_fire_cycle,
        cycle=cycle, waits=waits,
        occupancies={e: int(out.fifo[k])
                     for k, e in enumerate(sim.edge_list)},
        capacities={e: int(cap[e]) for e in sim.edge_list},
        consumed={n: int(out.consumed[node_of[n]]) for n in sim.node_ids},
        produced={n: int(out.produced[node_of[n]]) for n in sim.node_ids},
        profiled=p.profiled,
        replay_max_cycles=out.cycles + p.idle_bound + 64,
    )


# --------------------------------------------------------------------- #
# the total decision procedure
# --------------------------------------------------------------------- #
METHOD_REPLAY_ARGUMENT = "replay-argument"   # caps >= static bounds
METHOD_BOUNDED_REPLAY = "bounded-replay"     # exact NumPy re-execution


@dataclasses.dataclass
class CheckResult:
    """Total verdict for one capacity map: ``safe`` or ``deadlock``.

    ``safe`` carries the exact completion cycle (bit-identical to what
    ``run_sim`` reports under the same map); ``deadlock`` carries a
    replayable :class:`DeadlockCertificate`.  ``unknown`` does not exist.
    """

    verdict: str                     # VERDICT_SAFE | VERDICT_DEADLOCK
    method: str                      # how the verdict was decided
    completion_cycle: Optional[int]  # exact, when safe
    certificate: Optional[DeadlockCertificate]
    peak_occupancy: Dict[Edge, int]  # per-edge peak under this map

    @property
    def safe(self) -> bool:
        return self.verdict == VERDICT_SAFE

    def summary(self) -> str:
        if self.safe:
            return (f"safe ({self.method}): completes at cycle "
                    f"{self.completion_cycle}")
        return f"deadlock ({self.method}): {self.certificate.cycle_str()}"


def check_capacities(
    sim: CompiledSim, capacities: Dict[Edge, int], *,
    profiled: bool = False, analysis=None,
    _packed: Optional[_Packed] = None,
) -> CheckResult:
    """Decide one capacity map — always.

    Fast path: when every capacity meets its PR 9 schedule-preserving
    bound, the replay argument proves ``safe`` without executing a single
    cycle (the bounded run replays the unbounded schedule, so the
    completion cycle is ``analysis.predicted_cycles``).  That argument
    reasons about the *unprofiled* schedule, so with ``profiled=True``
    (Listing-2 interference shifts consume times and can deepen backlogs)
    the checker always falls through to the exact replay.
    """
    caps = {e: int(capacities.get(e, sim.capacity)) for e in sim.edge_list}
    if not profiled:
        if analysis is None:
            from .dataflow import analyze_sim

            analysis = analyze_sim(sim)
        if all(caps[e] >= b.capacity_lb for e, b in analysis.bounds.items()):
            return CheckResult(
                verdict=VERDICT_SAFE, method=METHOD_REPLAY_ARGUMENT,
                completion_cycle=analysis.predicted_cycles, certificate=None,
                peak_occupancy={e: b.peak_backlog
                                for e, b in analysis.bounds.items()})
    p = _packed if _packed is not None else _Packed(sim, profiled)
    out = bounded_replay(sim, caps, profiled=profiled, _packed=p)
    peaks = {e: int(out.peak[k]) for k, e in enumerate(sim.edge_list)}
    if out.completed:
        return CheckResult(verdict=VERDICT_SAFE,
                           method=METHOD_BOUNDED_REPLAY,
                           completion_cycle=out.cycles, certificate=None,
                           peak_occupancy=peaks)
    return CheckResult(verdict=VERDICT_DEADLOCK,
                       method=METHOD_BOUNDED_REPLAY, completion_cycle=None,
                       certificate=_build_certificate(p, caps, out),
                       peak_occupancy=peaks)


# --------------------------------------------------------------------- #
# exact minimal capacity synthesis
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class ExactSizingPlan:
    """A Pareto-minimal capacity plan from the model checker.

    Duck-typed like :class:`repro.trace.SizingPlan` (``capacity_map``,
    ``grown`` / ``shrunk`` / ``summary``) so it plugs into the same
    remediation seams, and additionally carries the minimal and
    conservative (PR 9) maps plus the replay budget that was spent.
    """

    advice: List                      # List[repro.trace.sizing.SizingAdvice]
    minimal: Dict[Edge, int]          # jointly-safe, per-edge minimal
    conservative: Dict[Edge, int]     # the PR 9 schedule-preserving bounds
    replays: int                      # bounded replays spent deciding
    profiled: bool

    def capacity_map(self, *, include_shrink: bool = False
                     ) -> Dict[Edge, int]:
        actions = ("grow", "shrink") if include_shrink else ("grow",)
        return {a.edge: a.recommended for a in self.advice
                if a.action in actions}

    @property
    def grown(self) -> List:
        return [a for a in self.advice if a.action == "grow"]

    @property
    def shrunk(self) -> List:
        return [a for a in self.advice if a.action == "shrink"]

    @property
    def words_saved_vs_bound(self) -> int:
        """FIFO words the exact plan saves over the conservative bounds."""
        return sum(self.conservative[e] - self.minimal[e]
                   for e in self.minimal)

    @property
    def best_ratio(self) -> float:
        """Largest conservative/minimal ratio across edges (>= 1.0)."""
        return max((self.conservative[e] / self.minimal[e]
                    for e in self.minimal), default=1.0)

    def summary(self) -> str:
        lines = [f"# exact sizing — {len(self.grown)} grow / "
                 f"{len(self.shrunk)} shrink; minimal total "
                 f"{sum(self.minimal.values())} words vs conservative "
                 f"{sum(self.conservative.values())} "
                 f"({self.words_saved_vs_bound} saved, "
                 f"{self.replays} replays)"]
        for a in self.advice:
            if a.action == "keep":
                continue
            lines.append(f"{'->'.join(a.edge):34s} {a.action:6s} "
                         f"{a.current:5d} -> {a.recommended:5d}  "
                         f"({a.reason})")
        return "\n".join(lines)

    def __str__(self) -> str:
        return self.summary()


def minimize_capacities(
    analysis, *, faults: Optional[FaultPlan] = None,
    overrides: Optional[Dict[Edge, int]] = None,
    profiled: bool = False, shrink: bool = True,
    overprovision_factor: int = 4,
) -> ExactSizingPlan:
    """Synthesize the exact minimal per-edge FIFO capacities.

    Starts from the PR 9 schedule-preserving bounds (a known-safe map) and
    binary-searches each edge down with the others pinned at their current
    values, reusing replays two ways: a deadlocked probe is a lower-bound
    witness, and every *safe* probe's peak occupancies immediately shrink
    every edge to ``peak + 1`` for free (the shrunk map replays the probe
    bit-for-bit).

    The final map ``M`` is **Pareto-minimal**: for every edge ``e``,
    ``M`` with ``M[e] - 1`` deadlocks.  Proof sketch: the binary search
    established a deadlock witness for ``M[e] - 1`` with the *other* edges
    at values that were pointwise >= their final ones, and deadlock is
    downward closed in the capacity lattice, so the witness survives every
    later shrink.  By the same monotonicity, growing any subset of edges
    above ``M`` (e.g. applying only the ``grow`` entries of the plan to a
    generously-capacitied base config) stays safe.

    With ``profiled=True`` the synthesis runs under Listing-2 profiling
    interference; the starting point is then verified by replay and widened
    to the demand bounds (producer total beats — backpressure-free by
    construction) in the rare case interference pushes a backlog past the
    unprofiled bound.
    """
    from repro.trace.sizing import GROW, KEEP, SHRINK, SizingAdvice

    from .dataflow import effective_capacities

    sim = analysis.sim
    p = _Packed(sim, profiled)
    node_of = {nid: i for i, nid in enumerate(sim.node_ids)}
    configured = effective_capacities(sim, faults, overrides)
    conservative = analysis.capacity_lower_bounds()

    minimal = dict(conservative)
    replays = 0

    def probe(caps: Dict[Edge, int]) -> ReplayOutcome:
        nonlocal replays
        replays += 1
        return bounded_replay(sim, caps, profiled=profiled, _packed=p)

    def harvest(caps: Dict[Edge, int], out: ReplayOutcome) -> Dict[Edge, int]:
        # peak+1 replays the safe probe identically => jointly safe
        return {e: min(caps[e], int(out.peak[k]) + 1)
                for k, e in enumerate(sim.edge_list)}

    if profiled:
        out0 = probe(minimal)
        if out0.completed:
            minimal = harvest(minimal, out0)
        else:
            # interference outgrew the unprofiled bounds: fall back to the
            # demand bounds, which remove backpressure entirely
            minimal = {e: max(conservative[e],
                              int(sim.total_out[node_of[e[0]]]))
                       for e in sim.edge_list}
            out0 = probe(minimal)
            if not out0.completed:
                raise RuntimeError(
                    "demand-bound capacities deadlocked — machine "
                    "invariants violated")
            minimal = harvest(minimal, out0)

    for edge in sorted(minimal, key=lambda e: -minimal[e]):
        lo, hi = 1, minimal[edge]
        while lo < hi:
            mid = (lo + hi) // 2
            trial = dict(minimal)
            trial[edge] = mid
            out = probe(trial)
            if out.completed:
                minimal = harvest(trial, out)
                hi = minimal[edge]
            else:
                lo = mid + 1
        minimal[edge] = hi

    advice: List[SizingAdvice] = []
    for e in sim.edge_list:
        cur, m = configured[e], minimal[e]
        if cur < m:
            advice.append(SizingAdvice(
                edge=e, current=cur, recommended=m, action=GROW,
                reason=f"exact minimal capacity {m} (model checker; "
                       f"conservative bound {conservative[e]})"))
        elif shrink and cur >= overprovision_factor * m + 1:
            advice.append(SizingAdvice(
                edge=e, current=cur, recommended=m, action=SHRINK,
                reason=f"exact minimal capacity {m} words "
                       f"(conservative bound {conservative[e]}); "
                       f"{cur - m} words of headroom buy nothing"))
        else:
            advice.append(SizingAdvice(
                edge=e, current=cur, recommended=cur, action=KEEP,
                reason="within exact minimal capacity"))
    return ExactSizingPlan(advice=advice, minimal=minimal,
                           conservative=conservative, replays=replays,
                           profiled=profiled)

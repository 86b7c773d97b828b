"""Plain NumPy reference of the RINN streaming machine.

It imports nothing of the program.  :func:`lower` reads a design (its layer
specs and edges, the data a designer hands the simulator) and the board
timing from the configuration file, and builds the machine's arrays itself;
:func:`simulate` steps that machine one cycle at a time, with the semantics
of SPRING's simulator as the paper describes it:

* an actor consumes one beat from every input FIFO when all hold one, its
  initiation-interval timer has expired and no injected stall covers the
  cycle; it produces one beat into every output FIFO while its pipeline
  allowance (fill, then rate out/in) is ahead and every output has space,
  capacities checked at the start of the cycle;
* the in-band profiler samples an input FIFO's occupancy just before a
  profiled actor reads it, and every ``pf_period``-th read of a profiled
  actor costs ``pf_stall`` more cycles (Listing 2's shared FSM state);
* faults: stall windows, a dropped or duplicated beat on an edge, capacity
  overrides, a bit flip of a stored profile word at a cycle;
* a run stops when every actor has produced everything, at ``max_cycles``,
  or after ``idle_limit`` cycles without a fire.

``interference=False`` is the control: the same machine without the
profiler's stall cycles, the shortcut a faster simulator would be tempted
to take.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import numpy as np

Edge = Tuple[str, str]

_BIG = np.int64(1) << 40
# layer kinds that emit only after consuming their whole input
_BURST = {"Dense", "Reshape", "Flatten"}
# layer kinds that carry no profiling tap
_UNPROFILED = {"Input", "Reshape", "Flatten"}


@dataclasses.dataclass
class Machine:
    nodes: List[str]
    edges: List[Edge]
    in_edges: np.ndarray      # [N, MAX_IN], E = no edge
    out_edges: np.ndarray     # [N, MAX_OUT]
    total_in: np.ndarray
    total_out: np.ndarray
    fill: np.ndarray
    ii: np.ndarray
    extra_lat: np.ndarray
    is_src: np.ndarray
    prof: np.ndarray
    capacity: int
    source_ii: int
    pf_period: int
    pf_stall: int


def _kind(spec) -> str:
    return type(spec).__name__.replace("Spec", "")


def _beats(shape) -> int:
    """io_stream beats of a tensor: one per pixel of an (H, W, C) map, one
    pack for a flat vector."""
    return shape[0] * shape[1] if len(shape) == 3 else 1


def _topo(nodes: List[str], edges: List[Edge]) -> List[str]:
    indeg = {n: 0 for n in nodes}
    for _, d in edges:
        indeg[d] += 1
    frontier = [n for n in nodes if indeg[n] == 0]
    order = []
    while frontier:
        n = frontier.pop(0)
        order.append(n)
        for s, d in edges:
            if s == n:
                indeg[d] -= 1
                if indeg[d] == 0:
                    frontier.append(d)
    if len(order) != len(nodes):
        raise ValueError("design has a cycle")
    return order


def _out_shape(kind: str, spec, ins):
    if kind == "Input":
        return tuple(spec.shape)
    if kind == "Dense":
        return (spec.units,)
    if kind == "Reshape":
        return tuple(spec.target)
    if kind == "Conv2D":
        return (ins[0][0], ins[0][1], spec.filters)
    if kind == "Flatten":
        return (math.prod(ins[0]),)
    if kind == "Concat":
        if len(ins[0]) == 3:
            return (ins[0][0], ins[0][1], sum(s[2] for s in ins))
        return (sum(s[0] for s in ins),)
    if kind in ("Add", "Clone", "Relu", "Sigmoid"):
        return tuple(ins[0])
    raise ValueError(f"the reference has no rule for layer kind {kind!r}")


def _ii(kind: str, spec, ins, timing: dict) -> int:
    rf = timing["reuse_factor"]
    if kind == "Dense":
        mults = ins[0][0] * spec.units
        return max(1, math.ceil(mults / max(1, mults // rf)))
    if kind == "Conv2D":
        mults = spec.kernel * spec.kernel * ins[0][2] * spec.filters
        return max(1, math.ceil(mults / max(1, mults // rf)))
    if kind == "Sigmoid":
        return timing["sigmoid_ii"]
    return 1


def lower(graph, timing: dict) -> Machine:
    """The machine of a design under a board's timing (a dict of the
    configuration file's ``timing``)."""
    names = list(graph.nodes)
    edges = [tuple(e) for e in graph.edges]
    order = _topo(names, edges)
    preds = {n: [s for s, d in edges if d == n] for n in order}
    succs = {n: [d for s, d in edges if s == n] for n in order}
    shape: Dict[str, tuple] = {}
    for n in order:
        spec = graph.nodes[n]
        shape[n] = _out_shape(_kind(spec), spec, [shape[p] for p in preds[n]])
    N, E = len(order), len(edges)
    eidx = {e: k for k, e in enumerate(edges)}
    max_in = max(1, max(len(preds[n]) for n in order))
    max_out = max(1, max(len(succs[n]) for n in order))
    in_e = np.full((N, max_in), E, np.int64)
    out_e = np.full((N, max_out), E, np.int64)
    z = lambda: np.zeros(N, np.int64)  # noqa: E731
    total_in, total_out, fill, extra = z(), z(), z(), z()
    ii = np.ones(N, np.int64)
    is_src = np.zeros(N, bool)
    prof = np.zeros(N, bool)
    for i, n in enumerate(order):
        spec, kind = graph.nodes[n], _kind(graph.nodes[n])
        for k, p in enumerate(preds[n]):
            in_e[i, k] = eidx[(p, n)]
        for k, d in enumerate(succs[n]):
            out_e[i, k] = eidx[(n, d)]
        ins = [shape[p] for p in preds[n]]
        in_beats = _beats(ins[0]) if ins else 0
        total_in[i], total_out[i] = in_beats, _beats(shape[n])
        is_src[i] = kind == "Input"
        prof[i] = kind not in _UNPROFILED and bool(ins)
        if is_src[i]:
            continue
        ii[i] = _ii(kind, spec, ins, timing)
        if (timing["bitwidth_ii_bump_threshold"]
                and timing["bitwidth"] >= timing["bitwidth_ii_bump_threshold"]
                and kind == "Add"):
            ii[i] += 1
        if kind in _BURST:
            fill[i] = in_beats
            if timing["output_register"] and kind == "Dense":
                extra[i] = 1
        elif kind == "Conv2D":
            fill[i] = min((spec.kernel - 1) * ins[0][1] + spec.kernel,
                          in_beats)
    return Machine(order, edges, in_e, out_e, total_in, total_out, fill, ii,
                   extra, is_src, prof, timing["fifo_capacity"],
                   timing["source_ii"], timing["pf_period"],
                   timing["pf_stall"])


def idle_limit(m: Machine, plan) -> int:
    """The longest quiet period a run may legitimately have: initiation
    intervals, source cadence and profiling stalls twice over, the drain
    latency, and the longest injected stall."""
    longest = max((s.duration for s in plan.stalls), default=0) if plan else 0
    return int(2 * (int(m.ii.max(initial=1)) + m.source_ii + m.pf_stall)
               + int(m.extra_lat.max(initial=0)) + longest + 16)


def simulate(m: Machine, *, plan=None,
             capacities: Optional[Dict[Edge, int]] = None,
             profiled: bool = False, max_cycles: int = 200_000,
             interference: bool = True) -> dict:
    """Run the machine cycle by cycle; returns the run's statistics keyed as
    the program's ``SimResult`` names them."""
    N, E = len(m.nodes), len(m.edges)
    node = {n: i for i, n in enumerate(m.nodes)}
    eidx = {e: k for k, e in enumerate(m.edges)}
    cap = np.full(E + 1, _BIG, np.int64)
    cap[:E] = m.capacity
    stalls: List[Tuple[int, int, int]] = []
    drop = np.full(E + 1, -1, np.int64)
    dup = np.full(E + 1, -1, np.int64)
    cor_cycle = np.full(E + 1, -1, np.int64)
    cor_mask = np.zeros(E + 1, np.int64)
    if plan is not None:
        for c in plan.capacities:
            cap[eidx[tuple(c.edge)]] = c.capacity
        stalls = [(node[s.node], s.start, s.start + s.duration)
                  for s in plan.stalls]
        for b in plan.drops:
            drop[eidx[tuple(b.edge)]] = b.beat
        for b in plan.dups:
            dup[eidx[tuple(b.edge)]] = b.beat
        for w in plan.corruptions:
            cor_cycle[eidx[tuple(w.edge)]] = w.cycle
            cor_mask[eidx[tuple(w.edge)]] = w.bitmask
    for e, c in (capacities or {}).items():
        cap[eidx[tuple(e)]] = c
    limit = idle_limit(m, plan)

    in_mask = m.in_edges < E
    out_mask = m.out_edges < E
    prof_node = m.prof & bool(profiled)
    pf_stall = m.pf_stall if interference else 0
    fifo = np.zeros(E + 1, np.int64)
    fifo[E] = 1
    maxf = fifo.copy()
    profmax = np.zeros(E + 1, np.int64)
    epush = np.zeros(E + 1, np.int64)
    consumed = np.zeros(N, np.int64)
    produced = np.zeros(N, np.int64)
    ii_t = np.zeros(N, np.int64)
    drain_t = m.extra_lat.copy()
    src_t = np.zeros(N, np.int64)
    cyc = idle = 0
    while (not (produced >= m.total_out).all() and cyc < max_cycles
           and idle < limit):
        stalled = np.zeros(N, bool)
        for i, a, b in stalls:
            stalled[i] |= a <= cyc < b
        in_counts = fifo[m.in_edges]
        in_avail = np.where(in_mask, in_counts >= 1, True).all(axis=1)
        consume = (in_avail & (ii_t == 0) & (consumed < m.total_in)
                   & ~m.is_src & ~stalled)
        reading = in_mask & (consume & prof_node)[:, None]
        np.maximum.at(profmax, m.in_edges[reading], in_counts[reading])
        consumed_next = consumed + consume
        done_in = consumed_next >= m.total_in
        prog = np.maximum(consumed_next - m.fill, 0)
        rate = np.where(m.total_out == m.total_in, prog,
                        prog * m.total_out // np.maximum(m.total_in, 1))
        allowed = np.where(done_in | m.is_src, m.total_out,
                           np.clip(rate, 0, m.total_out))
        out_space = np.where(out_mask, fifo[m.out_edges] < cap[m.out_edges],
                             True).all(axis=1)
        produce = ((produced < allowed) & out_space
                   & (~m.is_src | (src_t == 0)) & (drain_t == 0)
                   & (produced < m.total_out) & ~stalled)
        pops = np.bincount(m.in_edges[in_mask & consume[:, None]],
                           minlength=E + 1)
        pushes = np.bincount(m.out_edges[out_mask & produce[:, None]],
                             minlength=E + 1)
        landing = pushes > 0
        pushes = (pushes - (landing & (epush == drop))
                  + (landing & (epush == dup)))
        epush += landing
        fifo = fifo - pops + pushes
        fifo[E] = 1
        np.maximum(maxf, fifo, out=maxf)
        hit = cor_cycle == cyc
        profmax[hit] ^= cor_mask[hit]
        produced = produced + produce
        extra = np.where(prof_node & consume
                         & (consumed_next % m.pf_period == 0), pf_stall, 0)
        ii_t = np.where(consume, m.ii - 1 + extra, np.maximum(ii_t - 1, 0))
        drain_t = np.where(done_in & (drain_t > 0), drain_t - 1, drain_t)
        src_t = np.where(m.is_src & produce, m.source_ii - 1,
                         np.maximum(src_t - 1, 0))
        consumed = consumed_next
        idle = 0 if (consume.any() or produce.any()) else idle + 1
        cyc += 1

    completed = bool((produced >= m.total_out).all())
    dst = [node[d] for _, d in m.edges]
    return {
        "completed": completed,
        "cycles": cyc,
        "deadlocked": (not completed) and idle >= limit,
        "idle_cycles": idle,
        "fifo_max": {e: int(maxf[k]) for k, e in enumerate(m.edges)},
        "fifo_profiled": {e: int(profmax[k]) for k, e in enumerate(m.edges)
                          if profiled and m.prof[dst[k]]},
        "fifo_final": {e: int(fifo[k]) for k, e in enumerate(m.edges)},
        "node_consumed": {n: int(consumed[i]) for i, n in enumerate(m.nodes)},
        "node_produced": {n: int(produced[i]) for i, n in enumerate(m.nodes)},
    }


FIELDS = ("completed", "cycles", "deadlocked", "idle_cycles", "fifo_max",
          "fifo_profiled", "fifo_final", "node_consumed", "node_produced")


def differs(result, ref: dict) -> List[str]:
    """Fields of a program ``SimResult`` that differ from the reference."""
    return [f for f in FIELDS if getattr(result, f) != ref[f]]

"""Compile-once, batch-many runtime for the streaming dataflow simulator.

The historical ``run_sim`` baked fault plans, per-edge capacities, and the
``profiled`` flag into the trace as constants, so every call re-traced and
re-XLA-compiled the ``while_loop``.  Sweeps (Table I, Fig. 5, fault
campaigns, FIFOAdvisor-style remediation ladders) paid one compilation per
run and executed serially.

This module splits the machine into two runtime pytrees:

  * :class:`MachineOps` — the padded dataflow machine (topology, beat
    counts, timing).  Padded to a :class:`ShapeBucket` ``(N, E, MAX_IN,
    MAX_OUT, S)`` rounded up to powers of two, so *every graph that lands
    in the same bucket shares one XLA executable*.
  * :class:`FaultOps` — everything that varies between runs of the same
    machine: per-edge capacities (base + plan faults + remediation
    overrides), stall windows, drop/dup beat indices, profile-word
    corruption (cycle, mask), the ``profiled`` interference flag, and the
    loop bounds (``max_cycles``, ``idle_limit``).

One launch core packs a list of lanes, runs them as one device program and
unpacks the results.  One lane runs unbatched (``run_sim``); B lanes of
one machine run under ``jax.vmap`` with ``in_axes=(None, 0)``
(``run_sim_batch``: a fault campaign, a ladder rung or the cosim pair is
ONE program); B machines of one shape bucket run with ``in_axes=(0, 0)``
(``run_sim_many``).  Each program has a traced twin (``run_sim_traced*``).

Padding is semantically inert: padded actors have ``total_in = total_out =
0`` so they never consume, never produce, and count as finished; padded
edges are referenced by no actor and carry infinite capacity.  Lane masking
under ``vmap`` comes from JAX's ``while_loop`` batching rule (finished
lanes freeze), so batched results are bit-identical to sequential runs.

``compile_stats()`` exposes trace/launch counters so tests and the
benchmark can assert cache behaviour.  Every launch also writes two host
spans for ``jax.profiler``: ``sim.pack`` (packing the machine and its
lanes, up to the jitted call) and ``sim.unpack`` (the host outputs into
``SimResult``\\ s).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .streamsim import CompiledSim, FaultPlan, SimResult

Edge = Tuple[str, str]

_INF_CAP = np.iinfo(np.int32).max // 2


# --------------------------------------------------------------------- #
# shape buckets
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ShapeBucket:
    """Padded machine shape ``(N, E, MAX_IN, MAX_OUT, S)``; the jit cache key."""

    n: int
    e: int
    max_in: int
    max_out: int
    s: int


def _pow2_at_least(value: int, floor: int) -> int:
    return max(floor, 1 << max(0, value - 1).bit_length())


def machine_bucket(sim: CompiledSim, stall_slots: int = 1) -> ShapeBucket:
    """The shape bucket a compiled machine pads into.

    Two machines in the same bucket share one XLA executable per entry
    point; the floors (8 nodes/edges, 4 stall slots) keep small graphs and
    light fault plans from fragmenting the cache.
    """
    return ShapeBucket(
        n=_pow2_at_least(len(sim.node_ids), 8),
        e=_pow2_at_least(len(sim.edge_list), 8),
        max_in=_pow2_at_least(sim.in_edges.shape[1], 2),
        max_out=_pow2_at_least(sim.out_edges.shape[1], 2),
        s=_pow2_at_least(stall_slots, 4),
    )


def _stall_slots(plan: FaultPlan) -> int:
    counts: Dict[str, int] = {}
    for s in plan.stalls:
        counts[s.node] = counts.get(s.node, 0) + 1
    return max(counts.values(), default=1)


# --------------------------------------------------------------------- #
# runtime pytrees
# --------------------------------------------------------------------- #
class MachineOps(NamedTuple):
    """Padded machine arrays — runtime args, NOT trace constants."""

    in_edges: np.ndarray    # [N, MAX_IN] edge index, dummy = E (pad slot)
    out_edges: np.ndarray   # [N, MAX_OUT]
    total_in: np.ndarray    # [N]
    total_out: np.ndarray   # [N]
    fill: np.ndarray        # [N]
    ii: np.ndarray          # [N]
    extra_lat: np.ndarray   # [N]
    is_src: np.ndarray      # [N] bool
    prof: np.ndarray        # [N] bool — consumer-side SPRING tap
    pf_period: np.ndarray   # scalar
    pf_stall: np.ndarray    # scalar
    source_ii: np.ndarray   # scalar


class FaultOps(NamedTuple):
    """Per-run arrays: fault plan + capacities + flags + loop bounds."""

    cap: np.ndarray         # [E+1] per-edge capacity (dummy slot = inf)
    st_start: np.ndarray    # [N, S] stall window starts (-1 = none)
    st_end: np.ndarray      # [N, S]
    drop_beat: np.ndarray   # [E+1] beat index to drop (-1 = none)
    dup_beat: np.ndarray    # [E+1]
    cor_cycle: np.ndarray   # [E+1] profile-word corruption cycle (-1 = none)
    cor_mask: np.ndarray    # [E+1]
    profiled: np.ndarray    # scalar bool — in-band profiler attached
    idle_limit: np.ndarray  # scalar
    max_cycles: np.ndarray  # scalar


def pack_machine(sim: CompiledSim, bucket: ShapeBucket) -> MachineOps:
    """Pad the compiled machine into its bucket (numpy; device-ready)."""
    N, E = len(sim.node_ids), len(sim.edge_list)

    def pad_n(src, fill_value, dtype):
        out = np.full(bucket.n, fill_value, dtype)
        out[:N] = src
        return out

    in_edges = np.full((bucket.n, bucket.max_in), bucket.e, np.int32)
    in_edges[:N, :sim.in_edges.shape[1]] = np.where(
        sim.in_edges >= E, bucket.e, sim.in_edges)
    out_edges = np.full((bucket.n, bucket.max_out), bucket.e, np.int32)
    out_edges[:N, :sim.out_edges.shape[1]] = np.where(
        sim.out_edges >= E, bucket.e, sim.out_edges)
    return MachineOps(
        in_edges=in_edges, out_edges=out_edges,
        total_in=pad_n(sim.total_in, 0, np.int32),
        total_out=pad_n(sim.total_out, 0, np.int32),
        fill=pad_n(sim.fill, 0, np.int32),
        ii=pad_n(sim.ii, 1, np.int32),
        extra_lat=pad_n(sim.extra_lat, 0, np.int32),
        is_src=pad_n(sim.is_source, False, bool),
        prof=pad_n(sim.profiled, False, bool),
        pf_period=np.int32(sim.pf_period),
        pf_stall=np.int32(sim.pf_stall),
        source_ii=np.int32(sim.source_ii),
    )


def pack_faults(
    sim: CompiledSim, bucket: ShapeBucket, plan: FaultPlan,
    capacity_overrides: Optional[Dict[Edge, int]], profiled: bool,
    max_cycles: int,
) -> Tuple[FaultOps, np.ndarray, int]:
    """Lower one run's variable inputs to arrays.

    Returns ``(ops, cap_np, idle_limit)`` — ``cap_np`` and ``idle_limit``
    are kept host-side for result reporting / deadlock classification.
    """
    N, E = len(sim.node_ids), len(sim.edge_list)
    eidx = {e: i for i, e in enumerate(sim.edge_list)}
    node_of = {nid: i for i, nid in enumerate(sim.node_ids)}

    # capacity: base, then plan faults, then remediation overrides (win)
    cap = np.full(bucket.e + 1, _INF_CAP, np.int32)
    cap[:E] = sim.capacity
    for cf in plan.capacities:
        cap[eidx[cf.edge]] = cf.capacity
    for e, c in (capacity_overrides or {}).items():
        cap[eidx[e]] = c

    st_start = np.full((bucket.n, bucket.s), -1, np.int32)
    st_end = np.full((bucket.n, bucket.s), -1, np.int32)
    slot: Dict[str, int] = {}
    for s in plan.stalls:
        i, k = node_of[s.node], slot.get(s.node, 0)
        st_start[i, k], st_end[i, k] = s.start, s.start + s.duration
        slot[s.node] = k + 1

    drop_beat = np.full(bucket.e + 1, -1, np.int32)
    dup_beat = np.full(bucket.e + 1, -1, np.int32)
    for bf in plan.drops:
        drop_beat[eidx[bf.edge]] = bf.beat
    for bf in plan.dups:
        dup_beat[eidx[bf.edge]] = bf.beat

    cor_cycle = np.full(bucket.e + 1, -1, np.int32)
    cor_mask = np.zeros(bucket.e + 1, np.int32)
    for wc in plan.corruptions:
        cor_cycle[eidx[wc.edge]] = wc.cycle
        cor_mask[eidx[wc.edge]] = wc.bitmask

    # longest legitimate quiet period: ii timers, source cadence, profiling
    # stalls, drain latency, and any injected stall window
    idle_limit = int(
        2 * (int(sim.ii.max(initial=1)) + sim.source_ii + sim.pf_stall)
        + int(sim.extra_lat.max(initial=0)) + plan.max_stall() + 16)

    ops = FaultOps(
        cap=cap, st_start=st_start, st_end=st_end,
        drop_beat=drop_beat, dup_beat=dup_beat,
        cor_cycle=cor_cycle, cor_mask=cor_mask,
        profiled=np.bool_(profiled),
        idle_limit=np.int32(idle_limit),
        max_cycles=np.int32(max_cycles),
    )
    return ops, cap, idle_limit


def _to_device(tree):
    return jax.tree_util.tree_map(jnp.asarray, tree)


def _stack(trees):
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.asarray(np.stack(leaves)), *trees)


# --------------------------------------------------------------------- #
# the simulator core (pure; everything variable is a runtime argument)
# --------------------------------------------------------------------- #
_STATS = {"traces": 0, "launches": 0, "lanes": 0}


def compile_stats() -> Dict[str, int]:
    """Trace/launch counters.  ``traces`` increments only when XLA has to
    (re)compile; ``launches`` counts device program invocations; ``lanes``
    counts simulated runs (a batch of B adds B)."""
    return dict(_STATS)


def reset_compile_stats() -> None:
    for k in _STATS:
        _STATS[k] = 0


def _simulate(m: MachineOps, f: FaultOps, trace=None):
    """Step the machine to completion.

    ``trace=None`` is the plain path (unchanged).  ``trace=(stride,
    t_slots)`` additionally folds per-cycle FIFO state into ``t_slots``
    windowed accumulators of ``stride`` cycles each — within-window max
    and sum of occupancy, plus cycles-at-capacity / cycles-empty counts —
    the raw material of :mod:`repro.trace`.  ``t_slots`` is static (part
    of the jit cache key); ``stride`` is a runtime scalar.
    """
    _STATS["traces"] += 1  # python body runs only while tracing
    n_pad = m.total_in.shape[0]
    e_slots = f.cap.shape[0]  # E_pad + 1; last slot is the dummy edge
    dummy = e_slots - 1
    in_mask = m.in_edges < dummy
    out_mask = m.out_edges < dummy
    prof_node = m.prof & f.profiled
    if trace is not None:
        stride, t_slots = trace

    def body(state):
        (cyc, fifo, consumed, produced, ii_t, drain_t, src_t, maxf, profmax,
         epush, idle) = state[:11]
        stalled = jnp.any((cyc >= f.st_start) & (cyc < f.st_end), axis=1)
        in_counts = fifo[m.in_edges]                     # [N, MAX_IN]
        in_avail = jnp.all(jnp.where(in_mask, in_counts >= 1, True), axis=1)
        consume = (in_avail & (ii_t == 0) & (consumed < m.total_in)
                   & ~m.is_src & ~stalled)

        # SPRING sampling: data.size() read immediately before data.read()
        sampled = jnp.zeros(e_slots, fifo.dtype)
        read_now = consume & prof_node
        sampled = sampled.at[m.in_edges.reshape(-1)].max(
            jnp.where((in_mask & read_now[:, None]).reshape(-1),
                      in_counts.reshape(-1), 0))
        profmax = jnp.maximum(profmax, sampled)

        consumed_next = consumed + consume.astype(consumed.dtype)

        # pipeline allowance — generalized rate model: a node that maps
        # total_in beats to total_out beats produces at rate out/in after
        # its fill (1:1 nodes reduce to consumed - fill exactly)
        done_in = consumed_next >= m.total_in
        prog = jnp.maximum(consumed_next - m.fill, 0)
        safe_in = jnp.maximum(m.total_in, 1)
        rate_allowed = jnp.where(
            m.total_out == m.total_in, prog,
            (prog * m.total_out) // safe_in)
        allowed = jnp.where(done_in, m.total_out,
                            jnp.clip(rate_allowed, 0, m.total_out))
        allowed = jnp.where(m.is_src, m.total_out, allowed)

        out_counts = fifo[m.out_edges]
        out_space = jnp.all(
            jnp.where(out_mask, out_counts < f.cap[m.out_edges], True),
            axis=1)
        src_ready = jnp.where(m.is_src, src_t == 0, True)
        drain_ok = drain_t == 0
        produce = ((produced < allowed) & out_space & src_ready & drain_ok
                   & (produced < m.total_out) & ~stalled)

        pops = jnp.zeros(e_slots, fifo.dtype).at[m.in_edges.reshape(-1)].add(
            (in_mask & consume[:, None]).reshape(-1).astype(fifo.dtype))
        pushes = jnp.zeros(e_slots, fifo.dtype).at[
            m.out_edges.reshape(-1)].add(
            (out_mask & produce[:, None]).reshape(-1).astype(fifo.dtype))
        # wire faults: the producer fired, but the targeted beat never lands
        # (drop) or lands twice (dup) — invisible to its own bookkeeping
        will_push = pushes > 0
        drop_hit = will_push & (epush == f.drop_beat)
        dup_hit = will_push & (epush == f.dup_beat)
        pushes = (pushes - drop_hit.astype(fifo.dtype)
                  + dup_hit.astype(fifo.dtype))
        epush = epush + will_push.astype(epush.dtype)
        fifo = fifo - pops + pushes
        fifo = fifo.at[dummy].set(1)  # dummy slot stays at 1
        maxf = jnp.maximum(maxf, fifo)

        # in-fabric bit flip of the stored profile word at the fault cycle
        profmax = jnp.where(f.cor_cycle == cyc,
                            jnp.bitwise_xor(profmax, f.cor_mask), profmax)

        produced = produced + produce.astype(produced.dtype)

        # profiling interference (Listing 2): every pf_period-th firing of a
        # profiled node costs pf_stall extra cycles before the next consume.
        stall = jnp.where(
            prof_node & consume
            & (jnp.mod(consumed_next, m.pf_period) == 0),
            m.pf_stall, 0)
        ii_t = jnp.where(consume, m.ii - 1 + stall, jnp.maximum(ii_t - 1, 0))
        drain_t = jnp.where(done_in & (drain_t > 0), drain_t - 1, drain_t)
        src_fire = m.is_src & produce
        src_t = jnp.where(src_fire, m.source_ii - 1,
                          jnp.maximum(src_t - 1, 0))
        fired = jnp.any(consume) | jnp.any(produce)
        idle = jnp.where(fired, 0, idle + 1)
        nxt = (cyc + 1, fifo, consumed_next, produced, ii_t, drain_t, src_t,
               maxf, profmax, epush, idle)
        if trace is None:
            return nxt
        # windowed trace accumulators (end-of-cycle FIFO state)
        tr_max, tr_sum, tr_full, tr_empty, tr_cyc = state[11:]
        w = jnp.minimum(cyc // stride, t_slots - 1)
        at_cap = (fifo >= f.cap).astype(jnp.int32)
        tr_max = tr_max.at[w].max(fifo)
        tr_sum = tr_sum.at[w].add(fifo)
        tr_full = tr_full.at[w].add(at_cap)
        tr_empty = tr_empty.at[w].add((fifo == 0).astype(jnp.int32))
        tr_cyc = tr_cyc.at[w].add(1)
        return nxt + (tr_max, tr_sum, tr_full, tr_empty, tr_cyc)

    def cond(state):
        cyc, _fifo, _consumed, produced = state[:4]
        idle = state[10]
        done = jnp.all(produced >= m.total_out)
        return (~done) & (cyc < f.max_cycles) & (idle < f.idle_limit)

    z_e = jnp.zeros(e_slots, jnp.int32).at[dummy].set(1)
    z_n = jnp.zeros(n_pad, jnp.int32)
    state = (
        jnp.int32(0), z_e, z_n, z_n, z_n, m.extra_lat.astype(jnp.int32),
        z_n, z_e, jnp.zeros(e_slots, jnp.int32),
        jnp.zeros(e_slots, jnp.int32), jnp.int32(0),
    )
    if trace is not None:
        z_te = jnp.zeros((t_slots, e_slots), jnp.int32)
        state = state + (z_te, z_te, z_te, z_te,
                         jnp.zeros(t_slots, jnp.int32))
    state = jax.lax.while_loop(cond, body, state)
    (cyc, fifo, consumed, produced, _ii_t, _drain_t, _src_t, maxf, profmax,
     _epush, idle) = state[:11]
    outs = (cyc, fifo, consumed, produced, maxf, profmax, idle)
    if trace is not None:
        outs = outs + tuple(state[11:])
    return outs


@functools.lru_cache(maxsize=None)
def _program(windows: Optional[int], in_axes: Optional[Tuple]):
    """The jitted simulator for one window count (``None``: untraced) and
    one batching (``None``: one lane; else ``jax.vmap`` over ``(machine,
    faults)`` with these ``in_axes``).

    ``windows`` sizes the traced run's accumulators and is therefore part
    of the jit cache key; the window stride stays a runtime scalar, so
    re-running with a different stride (or machine in the same shape
    bucket) does not recompile.
    """
    def traced(m, f, stride):
        return _simulate(m, f, trace=(stride, windows))

    fn, tail = (_simulate, ()) if windows is None else (traced, (None,))
    if in_axes is not None:
        fn = jax.vmap(fn, in_axes=in_axes + tail)
    return jax.jit(fn)


# --------------------------------------------------------------------- #
# host-side result assembly
# --------------------------------------------------------------------- #
def _unpack(sim: CompiledSim, cap_np: np.ndarray, plan: Optional[FaultPlan],
            profiled: bool, idle_limit: int, outs) -> SimResult:
    cyc, fifo, consumed, produced, maxf, profmax, idle = outs
    N, E = len(sim.node_ids), len(sim.edge_list)
    node_of = {nid: i for i, nid in enumerate(sim.node_ids)}
    completed = bool((produced[:N] >= sim.total_out).all())
    fifo_max, fifo_prof, ctype, ffinal, fcap = {}, {}, {}, {}, {}
    for k, (s, d) in enumerate(sim.edge_list):
        fifo_max[(s, d)] = int(maxf[k])
        ctype[(s, d)] = sim.layer_type[d]
        ffinal[(s, d)] = int(fifo[k])
        fcap[(s, d)] = int(cap_np[k])
        if profiled and sim.profiled[node_of[d]]:
            fifo_prof[(s, d)] = int(profmax[k])
    idle_cycles = int(idle)
    return SimResult(
        completed=completed, cycles=int(cyc),
        fifo_max=fifo_max, fifo_profiled=fifo_prof, consumer_type=ctype,
        deadlocked=(not completed) and idle_cycles >= idle_limit,
        idle_cycles=idle_cycles,
        fifo_final=ffinal, fifo_capacity=fcap,
        node_consumed={n: int(consumed[i])
                       for i, n in enumerate(sim.node_ids)},
        node_produced={n: int(produced[i])
                       for i, n in enumerate(sim.node_ids)},
        faults=plan,
    )


class TraceBuffers(NamedTuple):
    """Raw windowed trace of one run — the feed for :mod:`repro.trace`.

    Arrays are trimmed to the windows the run actually touched and to the
    machine's real edges (padding removed); column ``k`` corresponds to
    ``edge_list[k]`` of the machine that produced it.
    """

    stride: int              # cycles per window
    occ_max: np.ndarray      # [W, E] within-window max occupancy
    occ_sum: np.ndarray      # [W, E] sum of end-of-cycle occupancies
    full_cycles: np.ndarray  # [W, E] cycles spent at capacity
    empty_cycles: np.ndarray # [W, E] cycles spent empty
    window_cycles: np.ndarray# [W] cycles folded into each window


def _trim_trace(sim: CompiledSim, stride: int, cycles: int,
                tr_outs) -> TraceBuffers:
    tr_max, tr_sum, tr_full, tr_empty, tr_cyc = [np.asarray(o)
                                                 for o in tr_outs]
    E = len(sim.edge_list)
    w_used = max(1, min(tr_cyc.shape[0],
                        -(-max(cycles, 1) // stride)))  # ceil
    return TraceBuffers(
        stride=stride,
        occ_max=tr_max[:w_used, :E], occ_sum=tr_sum[:w_used, :E],
        full_cycles=tr_full[:w_used, :E], empty_cycles=tr_empty[:w_used, :E],
        window_cycles=tr_cyc[:w_used])


def _launch(lanes: Sequence[tuple],
            trace: Optional[Tuple[int, int]] = None) -> list:
    """Pack, count, run and unpack one device program over ``lanes``, each
    ``(sim, plan, capacity_overrides, profiled, max_cycles)``.

    The program follows from what the lanes are: one lane runs unbatched,
    lanes of one machine run lane-vmapped, and lanes of several machines
    (which must share a shape bucket) run machine-vmapped.  ``trace`` is
    ``None`` or ``(windows, stride)``.  Returns one ``SimResult`` per lane
    in lane order, paired with its :class:`TraceBuffers` when traced.
    """
    sims = [lane[0] for lane in lanes]
    single = len(lanes) == 1
    one_machine = all(s is sims[0] for s in sims)
    with jax.profiler.TraceAnnotation("sim.pack"):
        bucket = machine_bucket(sims[0], max(
            _stall_slots(lane[1] or FaultPlan()) for lane in lanes))
        packed = [pack_faults(sim, bucket, plan or FaultPlan(), caps, prof, mc)
                  for sim, plan, caps, prof, mc in lanes]
        machine = (_to_device(pack_machine(sims[0], bucket)) if one_machine
                   else _stack([pack_machine(sim, bucket) for sim in sims]))
        ops = (_to_device(packed[0][0]) if single
               else _stack([p[0] for p in packed]))
    program = _program(None if trace is None else trace[0],
                       None if single else (None, 0) if one_machine
                       else (0, 0))
    args = (machine, ops) if trace is None else (
        machine, ops, jnp.int32(trace[1]))
    _STATS["launches"] += 1
    _STATS["lanes"] += len(lanes)
    outs = [np.asarray(o) for o in program(*args)]
    results = []
    with jax.profiler.TraceAnnotation("sim.unpack"):
        for b, (sim, plan, _, prof, _) in enumerate(lanes):
            lane_outs = outs if single else [o[b] for o in outs]
            res = _unpack(sim, packed[b][1], plan, prof, packed[b][2],
                          lane_outs[:7])
            results.append(res if trace is None else (res, _trim_trace(
                sim, trace[1], res.cycles, lane_outs[7:])))
    return results


# --------------------------------------------------------------------- #
# public entry points
# --------------------------------------------------------------------- #
def run_sim(
    sim: CompiledSim, profiled: bool = False, max_cycles: int = 200_000,
    faults: Optional[FaultPlan] = None,
    capacity_overrides: Optional[Dict[Edge, int]] = None,
) -> SimResult:
    """Execute the dataflow machine once; pure JAX control flow inside.

    ``faults`` injects the plan's stalls / beat faults / capacity faults /
    profile-word bit flips; ``capacity_overrides`` grows or shrinks specific
    edges' FIFOs (the remediation hook — it wins over the plan's capacity
    faults).  A no-progress detector stops the loop once no actor has fired
    for longer than any legitimate quiet period, so deadlocks terminate in
    O(deadlock cycle) rather than O(max_cycles).

    Fault plans, capacities, and the ``profiled`` flag are *runtime
    arguments* of a jit-cached executable keyed on the padded machine
    shape: re-running on the same shape bucket with a different plan /
    override / flag does not recompile.
    """
    return _launch([(sim, faults, capacity_overrides, profiled,
                     max_cycles)])[0]


def _broadcast(value, n: int, name: str) -> list:
    if isinstance(value, (list, tuple)):
        if len(value) != n:
            raise ValueError(f"{name} has {len(value)} entries, expected {n}")
        return list(value)
    return [value] * n


def _lanes(sims, plans, capacity_overrides, profiled, max_cycles,
           n: Optional[int] = None) -> List[tuple]:
    """Lane tuples.  Each argument is a sequence with one entry a lane (all
    sequences agree on length) or a scalar broadcast to every lane; ``n``
    forces the lane count when everything is scalar."""
    args = {"sims": sims, "plans": plans,
            "capacity_overrides": capacity_overrides, "profiled": profiled,
            "max_cycles": max_cycles}
    if n is None:
        n = max((len(v) for v in args.values()
                 if isinstance(v, (list, tuple))), default=1)
    return list(zip(*(_broadcast(v, n, k) for k, v in args.items())))


def run_sim_batch(
    sim: CompiledSim, *,
    plans: Union[None, FaultPlan, Sequence[Optional[FaultPlan]]] = None,
    capacity_overrides: Union[
        None, Dict[Edge, int], Sequence[Optional[Dict[Edge, int]]]] = None,
    profiled: Union[bool, Sequence[bool]] = False,
    max_cycles: Union[int, Sequence[int]] = 200_000,
    n: Optional[int] = None,
) -> List[SimResult]:
    """Run B fault/capacity/profiled lanes of one machine as a single
    vmapped device program.

    Any of ``plans`` / ``capacity_overrides`` / ``profiled`` / ``max_cycles``
    may be a sequence (all sequences must agree on length) or a scalar
    (broadcast).  ``n`` forces the lane count when everything is scalar.
    Results are bit-identical to calling :func:`run_sim` per lane.
    """
    return _launch(_lanes(sim, plans, capacity_overrides, profiled,
                          max_cycles, n))


def _trace_stride(stride: Optional[int], max_cycles: int, windows: int) -> int:
    if stride is not None:
        if stride < 1:
            raise ValueError("stride must be >= 1")
        return int(stride)
    return max(1, math.ceil(max_cycles / windows))


def run_sim_traced(
    sim: CompiledSim, *, profiled: bool = False, max_cycles: int = 200_000,
    faults: Optional[FaultPlan] = None,
    capacity_overrides: Optional[Dict[Edge, int]] = None,
    windows: int = 256, stride: Optional[int] = None,
) -> Tuple[SimResult, TraceBuffers]:
    """One run with windowed occupancy capture.

    The result is bit-identical to :func:`run_sim`; the extra
    :class:`TraceBuffers` holds per-window per-edge occupancy max/sum and
    full/empty cycle counts.  ``windows`` is static (one executable per
    distinct value — keep it at the default unless you need finer time
    resolution); ``stride`` defaults to ``ceil(max_cycles / windows)``.
    """
    stride = _trace_stride(stride, max_cycles, windows)
    return _launch([(sim, faults, capacity_overrides, profiled, max_cycles)],
                   (windows, stride))[0]


def run_sim_traced_batch(
    sim: CompiledSim, *,
    plans: Union[None, FaultPlan, Sequence[Optional[FaultPlan]]] = None,
    capacity_overrides: Union[
        None, Dict[Edge, int], Sequence[Optional[Dict[Edge, int]]]] = None,
    profiled: Union[bool, Sequence[bool]] = False,
    max_cycles: int = 200_000, n: Optional[int] = None,
    windows: int = 256, stride: Optional[int] = None,
) -> List[Tuple[SimResult, TraceBuffers]]:
    """B traced lanes of one machine in a single vmapped device program.

    Same broadcasting rules as :func:`run_sim_batch`; all lanes share one
    ``max_cycles`` / stride so their window axes line up (lane-to-lane
    diffing needs a common time base).
    """
    stride = _trace_stride(stride, max_cycles, windows)
    return _launch(_lanes(sim, plans, capacity_overrides, profiled,
                          max_cycles, n), (windows, stride))


def run_sim_many(
    sims: Sequence[CompiledSim], *,
    plans: Union[None, Sequence[Optional[FaultPlan]]] = None,
    capacity_overrides: Union[
        None, Sequence[Optional[Dict[Edge, int]]]] = None,
    profiled: Union[bool, Sequence[bool]] = False,
    max_cycles: Union[int, Sequence[int]] = 200_000,
) -> List[SimResult]:
    """Simulate many *different* machines, batching those that share a
    shape bucket into one vmapped launch (machine axis + fault axis).

    Used by the sweep drivers: a seed sweep or a timing sweep over
    same-shaped graphs becomes one device program instead of B serial runs.
    Machines in singleton buckets run as single lanes (still
    compile-cached).  Results come back in input order.
    """
    lanes = _lanes(list(sims), plans, capacity_overrides, profiled,
                   max_cycles)
    stall_slots = max(_stall_slots(lane[1] or FaultPlan()) for lane in lanes)
    groups: Dict[ShapeBucket, List[int]] = {}
    for i, lane in enumerate(lanes):
        groups.setdefault(machine_bucket(lane[0], stall_slots), []).append(i)

    results: List[Optional[SimResult]] = [None] * len(lanes)
    for idxs in groups.values():
        for i, res in zip(idxs, _launch([lanes[i] for i in idxs])):
            results[i] = res
    return results  # type: ignore[return-value]

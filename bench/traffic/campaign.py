"""Fault and capacity campaign lanes for one design.

Each lane is ``(plan, capacities, profiled)``.  A call holds ``lanes``
lanes: the first ``fault_free`` run capacity maps around the design's
minimal map (the map itself, two words more on every edge, one word less
on every edge, one word less on one edge at a time, and random safe maps
between), the rest also carry a seeded fault plan: a stall of one actor,
a bit flip of one stored profile word, and on some lanes a dropped or a
duplicated beat.  The lane counts, the maps and the mix are the same for
every seed; the seed draws the faults and the order of the maps.
"""
from __future__ import annotations

import random
from typing import Dict, List, Tuple

import numpy as np

Edge = Tuple[str, str]


def capacity_maps(minimal: Dict[Edge, int], rng) -> List[Dict[Edge, int]]:
    """Safe and short maps, interleaved: the minimal map is Pareto-minimal,
    so one word less on any edge deadlocks the design."""
    safe = [dict(minimal), {e: m + 2 for e, m in minimal.items()}]
    short = [{e: max(1, m - 1) for e, m in minimal.items()}]
    short += [{**minimal, e: m - 1} for e, m in minimal.items() if m > 1]
    while len(safe) < len(short):
        safe.append({e: m + int(rng.integers(0, 3))
                     for e, m in minimal.items()})
    return [m for pair in zip(safe, short) for m in pair]


def fault_plan(machine, rnd: random.Random, lane: int, horizon: int,
               spec: dict):
    from repro.rinn import BeatFault, FaultPlan, NodeStall, WordCorruption

    actors = [n for n, src in zip(machine.nodes, machine.is_src) if not src]
    dst = {n: i for i, n in enumerate(machine.nodes)}
    prof_edges = [e for e in machine.edges if machine.prof[dst[e[1]]]]
    lo, hi = spec["stall_span"]
    stalls = tuple(NodeStall(node=rnd.choice(actors),
                             start=rnd.randrange(1, horizon),
                             duration=rnd.randint(lo, hi))
                   for _ in range(spec["stalls"]))
    corruptions = tuple(WordCorruption(edge=rnd.choice(prof_edges),
                                       cycle=rnd.randrange(1, horizon))
                        for _ in range(spec["corruptions"]))
    beat = lambda: BeatFault(edge=rnd.choice(machine.edges),  # noqa: E731
                             beat=rnd.randrange(0, spec["beat_span"]))
    drops = (beat(),) if lane % spec["drop_every"] == 0 else ()
    dups = (beat(),) if lane % spec["dup_every"] == 1 else ()
    return FaultPlan(seed=lane, stalls=stalls, drops=drops, dups=dups,
                     corruptions=corruptions)


def calls(machine, traffic: dict, seed: int) -> List[list]:
    """The pool of calls a run cycles through, drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    minimal = {(s, d): int(m) for s, d, m in traffic["minimal_capacities"]}
    pool = []
    for _ in range(traffic["pool_calls"]):
        maps = capacity_maps(minimal, rng)
        rng.shuffle(maps)
        rnd = random.Random(int(rng.integers(0, 2**62)))
        lanes = [(None, maps[i % len(maps)],
                  i % traffic["profiled_fault_free_every"] == 1)
                 for i in range(traffic["fault_free"])]
        for j in range(traffic["lanes"] - traffic["fault_free"]):
            lanes.append((fault_plan(machine, rnd, j, traffic["horizon"],
                                     traffic["faults"]),
                          maps[j % len(maps)],
                          j % traffic["profiled_faulted_every"] == 0))
        pool.append(lanes)
    return pool

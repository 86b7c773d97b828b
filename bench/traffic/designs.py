"""Designs of the Table I RINN family, built and pinned by fingerprint.

A design is what a dataflow designer hands the simulator: a RINN graph of
layer specs and edges.  They are built with the program's own RINN
generator (the paper's construction), from the configuration's family
settings and a design seed.  Each built design is checked against a
fingerprint kept in the traffic file, so a change to the generator cannot
change the work a cell measures without failing the run.
"""
from __future__ import annotations

import hashlib
from typing import List

import numpy as np


def build(design: dict, seed: int):
    from repro.rinn import RinnConfig, generate_rinn

    return generate_rinn(RinnConfig(**{**design, "seed": int(seed)}))


def fingerprint(graph) -> str:
    """Short hash of a design's layer specs (in order) and edges."""
    text = repr([(n, repr(s)) for n, s in graph.nodes.items()])
    text += repr([tuple(e) for e in graph.edges])
    return hashlib.sha1(text.encode()).hexdigest()[:16]


def pinned(design: dict, seed: int, expected: str):
    graph = build(design, seed)
    got = fingerprint(graph)
    if got != expected:
        raise RuntimeError(
            f"design seed {seed} built to fingerprint {got}, the traffic "
            f"file pins {expected}: the RINN generator changed the work")
    return graph


def order(n: int, seed: int) -> List[int]:
    """The order in which a run cycles through ``n`` pool entries."""
    return [int(i) for i in np.random.default_rng(seed).permutation(n)]

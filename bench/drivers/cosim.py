"""Cosim driver: ``repro.rinn.compare(graph, timing, auto_remediate=True)``,
one call per design.

Set-up builds the traffic's pool of designs of the configuration's family
(each pinned by fingerprint), orders it from the seed, and compares every
design once, so every shape bucket the pool hits is compiled.  The window
cycles through the pool, one ``compare`` call per design, each timed from
outside; ``cosim_ms_p95`` is the 95th percentile of those latencies.

``correct`` compares reports drawn from the seed (and the slowest call's)
with the NumPy reference machine run unprofiled and profiled under the
capacities the report says both runs shared: completion cycles of both
lanes, and every row's cosim and profiled fullness.
"""
from __future__ import annotations

import statistics
import time
from typing import Dict, Tuple

import numpy as np

from bench.drivers import simcommon


def p95(values) -> float:
    """95th percentile, as ``statistics.quantiles`` puts it (inclusive)."""
    if len(values) < 2:
        return max(values)
    return statistics.quantiles(values, n=20, method="inclusive")[18]


class Driver(simcommon.SimDriver):
    span_name = "bench.cosim.call"

    def setup(self) -> None:
        from repro.rinn import compare

        from bench.traffic import designs

        t = self.traffic
        self.timing = simcommon.timing(self.config)
        pins = t["design_fingerprints"]
        self.pool = [(int(s), designs.pinned(self.config["design"], int(s),
                                             fp)) for s, fp in pins.items()]
        self.order = designs.order(len(self.pool), self.ctx.seed)
        self._run = lambda g: compare(  # noqa: E731
            g, self.timing, max_cycles=t["max_cycles"],
            auto_remediate=t["auto_remediate"])
        for _, graph in self.pool:
            self._run(graph)

    def one_call(self, k: int) -> dict:
        d = self.order[k % len(self.order)]
        rep = self._run(self.pool[d][1])
        return {"design": d, "report": rep}

    def end_to_end(self) -> Dict[str, float]:
        return {"cosim_ms_p95": 1e3 * p95([c["seconds"] for c in self.calls]),
                "setup_s": self.setup_s}

    def attempted_failed(self) -> Tuple[int, int]:
        return len(self.calls), 0

    def check(self) -> Dict[str, Tuple[float, float]]:
        from bench.reference import dataflow

        limits = self.ctx.workload["limits"]
        t0 = time.perf_counter()
        rng = np.random.default_rng(self.ctx.seed + 2)
        n = min(len(self.calls), limits["checked_calls"])
        picked = set(rng.choice(len(self.calls), n, replace=False).tolist())
        picked.add(int(np.argmax([c["seconds"] for c in self.calls])))
        refs: Dict[tuple, tuple] = {}
        bad = 0
        for k in sorted(picked):
            d, rep = self.calls[k]["design"], self.calls[k]["report"]
            caps = dict(rep.remediated_capacities)
            key = (d, tuple(sorted(caps.items())))
            if key not in refs:
                m = dataflow.lower(self.pool[d][1], self.config["timing"])
                refs[key] = tuple(
                    dataflow.simulate(m, capacities=caps, profiled=prof,
                                      max_cycles=self.traffic["max_cycles"])
                    for prof in (False, True))
            bad += not _matches(rep, *refs[key])
        self.check_s = time.perf_counter() - t0
        return {"reports_wrong": (bad, limits["reports_wrong"])}


def _matches(rep, ref: dict, prof: dict) -> bool:
    rows = {r.edge: (r.cosim, r.profiled) for r in rep.rows}
    want = {e: (ref["fifo_max"][e], v)
            for e, v in prof["fifo_profiled"].items()}
    return (rep.completed and ref["completed"] and prof["completed"]
            and rep.cycles_unprofiled == ref["cycles"]
            and rep.cycles_profiled == prof["cycles"] and rows == want)

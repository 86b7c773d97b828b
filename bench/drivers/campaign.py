"""Campaign driver: ``repro.rinn.run_sim_batch`` over one design.

Set-up builds the configuration's design (pinned by fingerprint), compiles
it for the board, draws the pool of calls from the seed (each a full lane
mix, ``bench/traffic/campaign.py``) and runs every pool call once, so each
shape bucket and lane count the window uses is compiled.  The window runs
the pool's calls back to back, in order, until ``--seconds`` have passed,
each timed from outside; results come back as host objects, so a call is
complete when it returns.

``correct`` compares lanes drawn from the seed among those the window
produced (and the longest lane of the first call) with the NumPy reference
machine, field by field.
"""
from __future__ import annotations

import time
from typing import Dict, Tuple

import numpy as np

from bench.drivers import simcommon


class Driver(simcommon.SimDriver):
    span_name = "bench.campaign.call"

    def setup(self) -> None:
        from repro.rinn import compile_graph, run_sim_batch

        from bench.reference import dataflow
        from bench.traffic import campaign, designs

        t = self.traffic
        self.timing = simcommon.timing(self.config)
        graph = designs.pinned(self.config["design"],
                               self.config["design"]["seed"],
                               t["design_fingerprint"])
        self.machine = dataflow.lower(graph, self.config["timing"])
        self.sim = compile_graph(graph, self.timing)
        self.pool = campaign.calls(self.machine, t, self.ctx.seed)
        self._run = lambda lanes: run_sim_batch(  # noqa: E731
            self.sim, plans=[p for p, _, _ in lanes],
            capacity_overrides=[c for _, c, _ in lanes],
            profiled=[f for _, _, f in lanes], max_cycles=t["max_cycles"])
        for lanes in self.pool:
            self._run(lanes)
        self.keep_rng = np.random.default_rng(self.ctx.seed + 1)

    def one_call(self, k: int) -> dict:
        p = k % len(self.pool)
        res = self._run(self.pool[p])
        cycles = [r.cycles for r in res]
        longest = int(np.argmax(cycles))
        keep = set(self.keep_rng.choice(len(res), self.ctx.workload["limits"]
                                        ["kept_lanes_per_call"],
                                        replace=False).tolist())
        keep.add(longest)
        return {"pool": p, "lanes": len(res), "iterations": max(cycles),
                "longest": longest,
                "kept": {j: res[j] for j in sorted(keep)}}

    def end_to_end(self) -> Dict[str, float]:
        lanes = sum(c["lanes"] for c in self.calls)
        return {"sim_lanes_per_s": lanes / self.window_s(),
                "setup_s": self.setup_s}

    def attempted_failed(self) -> Tuple[int, int]:
        return sum(c["lanes"] for c in self.calls), 0

    def check(self) -> Dict[str, Tuple[float, float]]:
        from bench.reference import dataflow

        limits = self.ctx.workload["limits"]
        t0 = time.perf_counter()
        kept = [(k, j) for k, c in enumerate(self.calls) for j in c["kept"]]
        rng = np.random.default_rng(self.ctx.seed + 2)
        n = min(len(kept), limits["checked_lanes"])
        picked = {kept[i] for i in rng.choice(len(kept), n, replace=False)}
        picked.add((0, self.calls[0]["longest"]))
        bad = 0
        for k, j in sorted(picked):
            plan, caps, profiled = self.pool[self.calls[k]["pool"]][j]
            ref = dataflow.simulate(self.machine, plan=plan, capacities=caps,
                                    profiled=profiled,
                                    max_cycles=self.traffic["max_cycles"])
            bad += bool(dataflow.differs(self.calls[k]["kept"][j], ref))
        self.check_s = time.perf_counter() - t0
        return {"lanes_wrong": (bad, limits["lanes_wrong"])}

"""What the simulator drivers share: the board timing, the timed window of
back-to-back calls, and the traced window."""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import List, Optional


def timing(config: dict):
    """The board timing the configuration states, checked against the
    program's profile of the same name."""
    import repro.rinn as rinn

    t = rinn.TimingProfile(**config["timing"])
    named = getattr(rinn, config["program"]["timing"])
    if dataclasses.asdict(named) != dataclasses.asdict(t):
        raise RuntimeError(f"the program's {config['program']['timing']} "
                           f"timing is not the configuration file's")
    return t


class SimDriver:
    """Calls back to back for ``--seconds``; subclasses define
    ``setup``, ``one_call(k) -> dict``, ``end_to_end``, ``check``."""

    span_name = "bench.sim.call"

    def __init__(self, ctx):
        self.ctx = ctx
        self.config, self.traffic = ctx.config, ctx.traffic
        self.calls: List[dict] = []
        self.setup_s: Optional[float] = None
        self.traces_in_window = 0
        self.records = {"calls": self.calls}

    def window(self, seconds: float) -> None:
        from repro.rinn import compile_stats

        before = compile_stats()
        self.t_start = time.perf_counter()
        self.setup_s = self.t_start - self.ctx.t0
        while True:
            with self.ctx.span(self.span_name):
                t_a = time.perf_counter()
                rec = self.one_call(len(self.calls))
                t_b = time.perf_counter()
            rec["seconds"] = t_b - t_a
            self.calls.append(rec)
            if t_b - self.t_start >= seconds:
                break
        self.t_end = t_b
        after = compile_stats()
        self.traces_in_window = after["traces"] - before["traces"]
        self.records["launches"] = after["launches"] - before["launches"]
        self.records["traces"] = self.traces_in_window
        print(f"bench: {len(self.calls)} calls, {self.traces_in_window} "
              f"compiles in the window", file=sys.stderr)

    def window_s(self) -> float:
        return self.t_end - self.t_start

    def trace_window(self, trace):
        from bench import tracing

        return tracing.span_window(trace, self.span_name)

    def release(self) -> None:
        pass

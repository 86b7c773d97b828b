#!/usr/bin/env python3
"""Read the numbers a cell's ``correct`` limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --controls 3

In one process, for each seed: the cell's own driver sets up, runs one
short window (``--seconds``) and checks, as a benchmark run does; the
checked numbers are the program's readings.  For the first ``--controls``
seeds the control is read too:

* served model: the float32 reference again in float8_e4m3, at each
  position of the same sampled rows; its reading is the gap of the token it
  puts first;
* simulator: the driver's calls answered by the reference machine without
  the profiler's stall cycles, then checked as usual.

Prints one JSON line per reading.  Never part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import run  # noqa: E402


def serve_control(driver) -> float:
    import numpy as np

    from bench.reference import chatglm

    call, rows = driver.sample()
    w = chatglm.make_weights(driver.config, call["seed"])
    toks = call["tokens"][rows]
    ref = np.asarray(chatglm.logits(driver.config, w, toks))
    ctrl = np.asarray(chatglm.logits(driver.config, w, toks, quant="fp8"))
    return chatglm.control_gap(ref, ctrl, driver.prompt_len)


def sim_control(driver) -> None:
    """Answer the driver's calls with the reference machine, interference
    off, in the program's place."""
    from repro.rinn import CosimReport, FifoRow, SimResult

    from bench.reference import dataflow

    cfg, max_cycles = driver.config, driver.traffic["max_cycles"]
    if hasattr(driver, "machine"):
        def batch(lanes):
            return [SimResult(consumer_type={}, **dataflow.simulate(
                driver.machine, plan=p, capacities=c, profiled=f,
                max_cycles=max_cycles, interference=False))
                for p, c, f in lanes]
        driver._run = batch
        return

    def compare(graph):
        m = dataflow.lower(graph, cfg["timing"])
        ref = dataflow.simulate(m, max_cycles=max_cycles)
        prof = dataflow.simulate(m, profiled=True, max_cycles=max_cycles,
                                 interference=False)
        rows = [FifoRow(e, "", ref["fifo_max"][e], v)
                for e, v in sorted(prof["fifo_profiled"].items())]
        return CosimReport(rows=rows, cycles_unprofiled=ref["cycles"],
                           cycles_profiled=prof["cycles"], completed=True)
    driver._run = compare


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1_000_003)
    ap.add_argument("--seconds", type=float, default=0.001)
    args = ap.parse_args(argv)

    cell, config, traffic, workload, _, _ = run.cell_setup(args.workload)
    run.configure_jax()
    devices = run.require_chips(int(cell["chips"]))
    from bench import counts

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        ctx = run.Context(args.workload, cell, config, traffic, workload,
                          seed, devices)
        ctx.t0 = time.perf_counter()
        ctx.peaks = counts.peaks(devices[0].device_kind)
        driver = run.load_module(run.BENCH / "drivers"
                                 / f"{traffic['driver']}.py").Driver(ctx)
        driver.setup()
        control = i < args.controls and traffic["driver"] != "serve"
        if control:
            sim_control(driver)
        driver.window(args.seconds)
        driver.release()
        line = {"workload": args.workload, "seed": seed,
                "side": "control" if control else "program",
                "checks": {k: v for k, (v, _) in driver.check().items()}}
        if i < args.controls and traffic["driver"] == "serve":
            line["control_logit_gap"] = serve_control(driver)
        print(json.dumps(line), flush=True)
        del driver


if __name__ == "__main__":
    main()

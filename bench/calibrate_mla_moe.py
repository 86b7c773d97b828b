#!/usr/bin/env python3
"""Read the numbers the latent-attention MoE cell's logit limit is set from,
on the chip.

    python3 bench/calibrate_mla_moe.py --workload moonlight.decode.inline \
        --seeds 3 --first-seed 1000003

In one process, for each seed: the cell's driver sets up, runs one short
window and checks, as a benchmark run does; its ``logit_gap_mean`` is
the program's reading.  Then, on the same sampled rows, the float32 reference
again in float8_e4m3 (the control: the gaps of the tokens it puts first)
and in bfloat16, with the token-layer expert selections that each rounding
changes against float32.  Gaps are summarised over the sampled rows'
generated positions: largest, mean, 99th and 90th percentiles, and the
share of positions whose token is not the reference's best.  Prints one
JSON line per seed.  Never part of a benchmark run.
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


def _stats(gaps) -> dict:
    """Summary of per-position logit gaps."""
    import numpy as np

    return {"max": float(gaps.max()), "mean": float(gaps.mean()),
            "p99": float(np.quantile(gaps, 0.99)),
            "p90": float(np.quantile(gaps, 0.9)),
            "share_off_best": float((gaps > 0).mean())}


def main(argv=None) -> None:
    import numpy as np

    from bench import counts
    from bench.reference import mla_moe

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1_000_003)
    ap.add_argument("--seconds", type=float, default=0.001)
    args = ap.parse_args(argv)

    cell, config, traffic, workload, _, _ = run.cell_setup(args.workload)
    run.configure_jax()
    devices = run.require_chips(int(cell["chips"]))
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        ctx = run.Context(args.workload, cell, config, traffic, workload,
                          seed, devices)
        ctx.t0 = time.perf_counter()
        ctx.peaks = counts.peaks(devices[0].device_kind)
        driver = run.load_module(run.BENCH / "drivers"
                                 / f"{traffic['driver']}.py").Driver(ctx)
        driver.setup()
        driver.window(args.seconds)
        driver.release()
        line = {"workload": args.workload, "seed": seed,
                "checks": {k: v for k, (v, _) in driver.check().items()}}
        call, rows = driver.sample()
        toks = call["tokens"][rows]
        w = mla_moe.make_weights(config, call["seed"])
        ref, sel = mla_moe.logits(config, w, toks, with_selections=True)
        ref = np.asarray(ref)
        P = driver.prompt_len
        line["program"] = _stats(mla_moe.position_gaps(ref, toks[:, P:], P))
        for quant in ("fp8", "bf16"):
            got, s = mla_moe.logits(config, w, toks, quant=quant,
                                    with_selections=True)
            picks = np.asarray(got)[:, P - 1:-1].argmax(-1)
            line[quant] = _stats(mla_moe.position_gaps(ref, picks, P))
            line[f"{quant}_selections_differ"] = mla_moe.selections_differ(
                sel, s)
        line["selections"] = int(np.prod(sel.shape[:-1]))
        del w
        print(json.dumps(line), flush=True)
        del driver


if __name__ == "__main__":
    main()

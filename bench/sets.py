#!/usr/bin/env python3
"""Run a cell several times, one process per run, and report the spreads
its bounds are set from.

    python3 bench/sets.py --workload <cell> --seeds 11 22 33 --sets 2 \
        --seconds 10 [--trace 1] [--out chiprun_out/sets.jsonl]

Each set runs every seed once, in order; the sets use the same seeds.  For
each metric and set it prints the median and the spread: the distance
between the first and third quartiles (``statistics.quantiles(v, n=4)``)
as a share of the median.  Never part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def spread(values) -> float:
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", default="10")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    results = []
    for s in range(args.sets):
        for seed in args.seeds:
            t = time.perf_counter()
            proc = subprocess.run(
                [sys.executable, "bench/run.py", "--workload", args.workload,
                 "--seed", str(seed), "--seconds", args.seconds,
                 "--trace", args.trace],
                cwd=ROOT, capture_output=True, text=True)
            wall = time.perf_counter() - t
            lines = proc.stdout.strip().splitlines()
            line = {"set": s, "seed": seed, "rc": proc.returncode,
                    "wall_s": wall}
            try:
                line.update(json.loads(lines[-1]))
            except (IndexError, json.JSONDecodeError):
                line["stderr"] = proc.stderr[-3000:]
            results.append(line)
            print(json.dumps(line), flush=True)
            if args.out:
                with open(ROOT / args.out, "a") as f:
                    f.write(json.dumps(line) + "\n")
    names = sorted({m for r in results for m in r.get("metrics", {})})
    for s in range(args.sets):
        rows = [r for r in results if r["set"] == s and "metrics" in r]
        for m in names:
            v = [r["metrics"][m]["value"] for r in rows
                 if m in r["metrics"]]
            if len(v) >= 2:
                print(f"set {s} {m}: median {statistics.median(v)!r} "
                      f"spread {spread(v)!r} n {len(v)} values {v}")
        print(f"set {s} correct: {[r.get('correct') for r in rows]}")


if __name__ == "__main__":
    main()

"""Benchmark driver: one module per paper table/figure + the roofline.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig3 table1
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

BENCHES = ("fig3", "fig4", "table1", "fig5", "roofline", "trace_smoke",
           "analysis_smoke")


def main() -> None:
    which = [a for a in sys.argv[1:] if not a.startswith("-")] or list(BENCHES)
    out_dir = Path("artifacts/bench")
    out_dir.mkdir(parents=True, exist_ok=True)
    results = {}
    for name in which:
        t0 = time.time()
        if name == "fig3":
            from benchmarks import fig3_overhead as mod
        elif name == "fig4":
            from benchmarks import fig4_precision as mod
        elif name == "table1":
            from benchmarks import table1_cosim as mod
        elif name == "fig5":
            from benchmarks import fig5_patterns as mod
        elif name == "roofline":
            from benchmarks import roofline as mod
        elif name == "trace_smoke":
            from benchmarks import trace_smoke as mod
        elif name == "analysis_smoke":
            from benchmarks import analysis_smoke as mod
        else:
            raise SystemExit(f"unknown benchmark {name!r}; have {BENCHES}")
        res = mod.run()
        dt = time.time() - t0
        results[name] = res
        (out_dir / f"{name}.json").write_text(json.dumps(res, indent=1,
                                                         default=str))
        print(f"[bench] {name} done in {dt:.1f}s -> artifacts/bench/{name}.json")
    print(f"[bench] completed: {', '.join(results)}")


if __name__ == "__main__":
    main()

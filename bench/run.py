#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything the
run needs is found by name:

* ``bench/configs/<config>.json``   the model or deployment, as run;
* ``bench/traffic/<traffic>.json``  the traffic mix, naming its driver;
* ``bench/drivers/<driver>.py``     the client of the program's entry that
                                    sets up, drives the window and checks;
* ``bench/workloads/<cell>.json``   the cell's limits for ``correct`` and
                                    its traced window;
* ``bench/metrics/<metric>.py``     one reader per per-layer metric.

One process per run: set up and warm up (``setup_s``), measure for
``--seconds``, free the program's state, check the outputs against the
plain reference, print the numbers compared on standard error and, as the
last line of standard output, one JSON object.  ``--trace 1`` runs a short
window under the profiler and reports the per-layer metrics instead of the
end-to-end ones.  With no TPU, or fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"


class RunError(Exception):
    """The run cannot produce a result; nothing is printed on stdout."""


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.is_file():
        raise RunError(f"missing {path.relative_to(ROOT)}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise RunError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def cell_setup(name: str):
    """The cell's entry, metric definitions and data files."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    workload = load_json(BENCH / "workloads" / f"{name}.json")
    applies = lambda m: name in m.get("workloads", [name])  # noqa: E731
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    layer = [m for m in bench["per_layer"] if applies(m)]
    return cell, config, traffic, workload, e2e, layer


def require_chips(n: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RunError(f"no TPU: JAX sees {devices[0].platform} devices")
    if len(devices) < n:
        raise RunError(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices[:n]


def configure_jax() -> None:
    """Persistent compilation cache at a fixed path, every program cached."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR))
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class Context:
    """What the driver and the metric readers are given."""

    def __init__(self, name, cell, config, traffic, workload, seed, devices):
        self.name, self.cell = name, cell
        self.config, self.traffic, self.workload = config, traffic, workload
        self.seed = seed
        self.devices = devices
        self.t0 = T0
        self.trace = None          # bench.tracing.Trace of a traced window
        self.peaks = None          # bench/peaks.json row of this device
        self.driver = self.records = self.driver_window = None

    @staticmethod
    def span(label: str):
        """A host span in the profiler's trace (a no-op when not tracing)."""
        import jax

        return jax.profiler.TraceAnnotation(label)


def traced_window(driver, seconds: float):
    import jax

    from bench import tracing

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        driver.window(seconds)
    finally:
        jax.profiler.stop_trace()
    try:
        return tracing.load(tracing.find_xplane(str(TRACE_DIR)))
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)


def per_layer(ctx, driver, metrics) -> dict:
    out = {}
    for m in metrics:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx, driver.records)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_info(devices, busy_window=None) -> dict:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}
    if busy_window is not None:
        info["busy_s"], info["window_s"] = busy_window
    return info


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        raise RunError("the program (src/repro) is not in this checkout")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    cell, config, traffic, workload, e2e, layer = cell_setup(args.workload)
    configure_jax()
    devices = require_chips(int(cell["chips"]))

    from bench import counts, tracing

    ctx = Context(args.workload, cell, config, traffic, workload, args.seed,
                  devices)
    ctx.peaks = counts.peaks(devices[0].device_kind)
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py"
                         ).Driver(ctx)
    ctx.driver, ctx.records = driver, driver.records
    ctx.driver_window = driver.trace_window
    driver.setup()
    busy_window = None
    if args.trace:
        ctx.trace = traced_window(driver, workload["trace_seconds"])
        window = driver.trace_window(ctx.trace)
        if window is None:
            raise RunError("the traced window holds none of the cell's work")
        lo, hi = window
        busy_window = (tracing.busy_ns(ctx.trace, lo, hi) * 1e-9,
                       (hi - lo) * 1e-9)
        if busy_window[0] <= 0:
            raise RunError("no operation ran on the device in the window")
    else:
        driver.window(args.seconds)
    device = device_info(devices, busy_window)
    if args.trace:
        metrics = per_layer(ctx, driver, layer)
    else:
        values = driver.end_to_end()
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e}
    attempted, failed = driver.attempted_failed()
    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        lo, hi = driver.trace_window(ctx.trace)
        result["breakdown"] = {
            "device_ops": [list(x) for x in tracing.top_ops(ctx.trace, lo, hi)],
            "idle_gaps": [list(x) for x in tracing.idle_gaps(ctx.trace, lo, hi)],
        }
    ctx.trace = None
    driver.release()
    checks = driver.check()
    result["correct"] = all(v <= lim for v, lim in checks.values())
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    try:
        result = run(argv)
    except RunError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

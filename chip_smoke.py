#!/usr/bin/env python3
"""Smoke run of the system's main paths on TPU chips.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded training path on four

One chip, in order:
  device     JAX must see a TPU; there is no CPU fallback.
  serve      ``run_serve`` on chatglm3-6b at its published widths (all 28
             layers, ~12.5 GB of bf16 weights from a seed), inline
             profiling: 8 requests of 128 prompt + 32 generated tokens.
             Any profiling degradation or integrity failure fails the run.
  kv-cache   the same weights: a 64-token prompt streamed through
             ``decode_fn`` must give the last-position logits of one
             ``prefill_fn`` call.
  simulator  a 256-lane fault/capacity campaign of one RINN through
             ``run_sim_batch``; fault-free lanes must match the independent
             NumPy executor ``bounded_replay``, faulted lanes must be
             bit-identical to one-lane ``run_sim`` calls.

``--four-chips`` runs only ``run_train`` on a 2x2 (data, model) mesh:
chatglm3-6b at published widths cut to 12 layers for 3 steps (weights and
Adam state spread over the four chips), then the same seeds at 2 layers on
one device and on the four-device mesh, whose losses and gradient norms
must agree.

Times and memory printed here are smoke readings, not benchmark numbers.
Any failed check raises: the traceback is printed and the exit code is not
0.  The last line of a passing run is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SEED = 0
SERVE_ARCH = "chatglm3-6b"
SERVE_BATCH, PROMPT_LEN, GEN = 8, 128, 32
CACHE_BATCH, CACHE_TOKENS = 8, 64
# bf16 keeps 8 significant bits (relative step 2**-8 = 0.4%).  Prefill and
# the token-by-token decode use the same weights but round at different
# points (one [64, d] matmul against 64 [1, d] ones, softmax over a masked
# block against softmax over the cache) through 28 layers, so their logits
# are expected to differ by a few percent of the logits' range.  A cache
# that holds the wrong positions or misses the rotary phase differs by the
# order of the logits themselves.
CACHE_REL_TOL = 0.05
SIM_CONFIG = dict(n_backbone=9, image_size=8, pattern="long_skip",
                  density=0.4, seed=21)
SIM_LANES, SIM_FAULT_FREE, SIM_SINGLE_CHECKS = 256, 64, 8
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 8, 512
FOUR_CHIP_LAYERS, COMPARE_LAYERS = 12, 2
# The four-device program splits every matmul and the gradient reduction
# across chips and sums the parts in another order; in bf16 that moves the
# loss by far less than 1%.  Adam's first steps have size lr whatever the
# gradient's magnitude, so elements whose gradients are nearly zero can step
# either way; that moves later gradient norms by a few percent at most.
LOSS_REL_TOL, GNORM_REL_TOL = 0.01, 0.05


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def require_tpu(n_chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX sees "
                         f"{devices[0].platform} devices); nothing was run")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} TPU chips, JAX sees "
                         f"{len(devices)}")
    say("device", f"platform={devices[0].platform} "
                  f"kind={devices[0].device_kind} count={len(devices)}")
    return devices


# --------------------------------------------------------------------- #
# one chip
# --------------------------------------------------------------------- #
def serve_phase(arch: str, *, reduced: bool, batch: int, prompt_len: int,
                gen: int) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch.serve import run_serve
    from repro.models import param_bytes
    from repro.models.api import model_specs

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    say("serve", f"{arch}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
                 f"{param_bytes(model_specs(cfg)) / 1e9:.2f} GB of weights")
    res = run_serve(arch, reduced=reduced, batch=batch,
                    prompt_len=prompt_len, gen=gen, seed=SEED,
                    profile_policy="inline")
    toks = np.asarray(res.tokens)
    check(toks.shape == (batch, prompt_len + gen),
          f"tokens have shape {toks.shape}")
    check(bool((toks >= 0).all() and (toks < cfg.vocab_size).all()),
          f"tokens outside [0, {cfg.vocab_size})")
    check(not res.supervisor.events,
          f"profiling degraded: {res.supervisor.summary()}")
    check(res.collector.integrity_failures == 0,
          f"{res.collector.integrity_failures} profile streams failed "
          f"their integrity check")
    stats = jax.devices()[0].memory_stats() or {}
    say("serve", f"tokens {toks.shape} in [0, {cfg.vocab_size}); "
                 f"{res.supervisor.summary()}; no integrity failures")
    say("serve", f"smoke readings (not benchmark numbers): compile "
                 f"{res.compile_s:.2f} s; decode {res.step_s * 1e3:.2f} ms "
                 f"per step (batch {batch}, with inline profiling, mean of "
                 f"the last {gen - 1} generated steps to block_until_ready); "
                 f"peak device memory "
                 f"{stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB")


def cache_phase(arch: str, *, reduced: bool, batch: int, n_tokens: int
                ) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import init_params
    from repro.models.api import decode_fn, init_caches, model_specs, prefill_fn

    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    params = init_params(model_specs(cfg), jax.random.PRNGKey(SEED))
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 2),
                                (batch, n_tokens), 0, cfg.vocab_size)
    prefill = jax.jit(lambda p, t: prefill_fn(cfg, p, {"tokens": t})[0])
    want = np.asarray(prefill(params, tokens), np.float32)[:, -1]

    decode = jax.jit(lambda p, c, t, pos: decode_fn(cfg, p, c, t, pos)[:2],
                     donate_argnums=(1,))
    caches = init_caches(cfg, batch, n_tokens)
    for pos in range(n_tokens):
        logits, caches = decode(params, caches, tokens[:, pos:pos + 1], pos)
    got = np.asarray(logits, np.float32)[:, -1]

    v = cfg.vocab_size
    want, got = want[:, :v], got[:, :v]
    check(bool(np.isfinite(want).all() and np.isfinite(got).all()),
          "non-finite logits")
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    agree = float((got.argmax(-1) == want.argmax(-1)).mean())
    say("kv-cache", f"{n_tokens} decode steps vs one prefill: max |diff| / "
                    f"max |logit| = {rel:.4f} (tolerance {CACHE_REL_TOL}); "
                    f"argmax agrees on {agree:.0%} of {batch} rows")
    check(rel <= CACHE_REL_TOL,
          f"decode and prefill logits differ by {rel:.4f} of their range")


def _campaign(sim, analysis, lanes: int, fault_free: int):
    """Lane specs ``(plan, capacities, profiled)``: capacity maps at, above
    and below the exact minimal map, alone and under seeded fault plans."""
    import numpy as np

    from repro.analysis import minimize_capacities
    from repro.rinn import FaultPlan

    minimal = minimize_capacities(analysis).minimal
    rng = np.random.default_rng(SEED)
    safe = [dict(minimal), {e: m + 2 for e, m in minimal.items()}]
    # the minimal map is Pareto-minimal: one word less on any edge deadlocks
    short = [{e: max(1, m - 1) for e, m in minimal.items()}]
    short += [{**minimal, e: m - 1} for e, m in minimal.items() if m > 1]
    while len(safe) < len(short):
        safe.append({e: m + int(rng.integers(0, 3))
                     for e, m in minimal.items()})
    maps = [m for pair in zip(safe, short) for m in pair]
    specs = [(None, maps[i % len(maps)], bool(i % 4 >= 2))
             for i in range(fault_free)]
    horizon = int(analysis.predicted_cycles)
    for i in range(lanes - fault_free):
        plan = FaultPlan.generate(sim, seed=1000 + i, horizon=horizon)
        specs.append((plan, maps[i % len(maps)], bool(i % 3 == 0)))
    return specs


def sim_phase(lanes: int, fault_free: int, single_checks: int) -> None:
    import numpy as np

    from repro.analysis import analyze_sim, bounded_replay
    from repro.analysis.modelcheck import _Packed
    from repro.rinn import (RinnConfig, ZCU102, compile_graph, generate_rinn,
                            run_sim, run_sim_batch)

    sim = compile_graph(generate_rinn(RinnConfig(**SIM_CONFIG)), ZCU102)
    analysis = analyze_sim(sim)
    specs = _campaign(sim, analysis, lanes, fault_free)
    t0 = time.perf_counter()
    results = run_sim_batch(sim, plans=[s[0] for s in specs],
                            capacity_overrides=[s[1] for s in specs],
                            profiled=[s[2] for s in specs])
    dt = time.perf_counter() - t0
    n_dead = sum(not r.completed for r in results)
    say("simulator", f"{len(sim.node_ids)} actors, {len(sim.edge_list)} "
                     f"FIFOs; {lanes} lanes in one run_sim_batch call "
                     f"({dt:.2f} s with compile, a smoke reading); "
                     f"{n_dead} lanes deadlocked")
    check(0 < n_dead < lanes, "the campaign must mix completing and "
                              "deadlocking lanes")

    for i, ((plan, caps, profiled), res) in enumerate(
            zip(specs[:fault_free], results)):
        packed = _Packed(sim, profiled)
        ref = bounded_replay(sim, caps, profiled=profiled, _packed=packed)
        # a deadlocked simulator lane idles idle_bound cycles past its last
        # fire before it stops; the replay stops at the fixpoint itself
        cycles = (ref.cycles if ref.completed
                  else ref.last_fire_cycle + packed.idle_bound)
        maxima = {e: int(ref.peak[k]) for k, e in enumerate(sim.edge_list)}
        check(res.completed == ref.completed and res.cycles == cycles
              and res.fifo_max == maxima,
              f"lane {i}: simulator (completed={res.completed}, cycles="
              f"{res.cycles}) differs from bounded_replay (completed="
              f"{ref.completed}, cycles={cycles}) or in its FIFO maxima")
    for i in np.linspace(fault_free, lanes - 1, single_checks).astype(int):
        plan, caps, profiled = specs[i]
        one = run_sim(sim, profiled=profiled, faults=plan,
                      capacity_overrides=caps)
        check(one == results[i], f"faulted lane {i} differs from run_sim")
    say("simulator", f"{fault_free} fault-free lanes match bounded_replay "
                     f"(completed, cycles, per-FIFO maxima); "
                     f"{single_checks} faulted lanes bit-identical to "
                     f"run_sim")


# --------------------------------------------------------------------- #
# four chips
# --------------------------------------------------------------------- #
def _bytes_per_device(tree) -> dict:
    import jax

    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) + \
                shard.data.nbytes
    return out


def four_chip_phase(devices, arch: str, *, reduced: bool, layers: int,
                    compare_layers: int, steps: int, batch: int, seq: int
                    ) -> None:
    import math

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import run_train

    base = get_config(arch)
    if reduced:
        base = base.reduced()
    mesh4 = make_host_mesh(model=2, devices=devices[:4])
    say("four-chips", f"mesh {dict(mesh4.shape)} over devices "
                      f"{[d.id for d in mesh4.devices.flat]}")

    cfg = dataclasses.replace(base, n_layers=layers)
    t0 = time.perf_counter()
    res = run_train(cfg, mesh=mesh4, steps=steps, batch=batch, seq=seq,
                    seed=SEED)
    dt = time.perf_counter() - t0
    check(len(res.losses) == steps and all(map(math.isfinite, res.losses)),
          f"losses {res.losses}")
    per_dev = _bytes_per_device(res.params)
    total = sum(math.prod(x.shape) * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(res.params))
    check(sorted(per_dev) == sorted(d.id for d in devices[:4]),
          f"parameters on devices {sorted(per_dev)}, not all four")
    check(max(per_dev.values()) < 0.5 * total,
          f"one device holds {max(per_dev.values())} of {total} parameter "
          f"bytes: the weights are not sharded")
    say("four-chips", f"{arch} (d_model {cfg.d_model}, {layers} layers): "
                      f"{steps} steps in {dt:.1f} s with compile (a smoke "
                      f"reading); losses {res.losses}; grad norms "
                      f"{res.grad_norms}")
    say("four-chips", f"parameter bytes per device "
                      f"{ {k: per_dev[k] for k in sorted(per_dev)} } of "
                      f"{total} in all")

    cfg = dataclasses.replace(base, n_layers=compare_layers)
    one = run_train(cfg, mesh=make_host_mesh(devices=devices[:1]),
                    steps=steps, batch=batch, seq=seq, seed=SEED)
    four = run_train(cfg, mesh=mesh4, steps=steps, batch=batch, seq=seq,
                     seed=SEED)
    loss_rel = np.abs(np.subtract(four.losses, one.losses)) / np.abs(
        one.losses)
    gn_rel = np.abs(np.subtract(four.grad_norms, one.grad_norms)) / np.abs(
        one.grad_norms)
    say("four-chips", f"{compare_layers} layers, one device vs four: losses "
                      f"{one.losses} vs {four.losses} (max rel "
                      f"{loss_rel.max():.2e}, tolerance {LOSS_REL_TOL}); "
                      f"grad norms {one.grad_norms} vs {four.grad_norms} "
                      f"(max rel {gn_rel.max():.2e}, tolerance "
                      f"{GNORM_REL_TOL})")
    check(float(loss_rel.max()) <= LOSS_REL_TOL, "losses disagree")
    check(float(gn_rel.max()) <= GNORM_REL_TOL, "grad norms disagree")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training path on four chips")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: the repro package is not at "
                         f"{SRC / 'repro'}; run from a checkout of the repo")
    sys.path.insert(0, str(SRC))

    n_chips = 4 if args.four_chips else 1
    devices = require_tpu(n_chips)
    from repro.launch.compile_cache import configure_compile_cache

    say("device", f"compile cache: {configure_compile_cache()}")
    if args.four_chips:
        four_chip_phase(devices, SERVE_ARCH, reduced=False,
                        layers=FOUR_CHIP_LAYERS,
                        compare_layers=COMPARE_LAYERS, steps=TRAIN_STEPS,
                        batch=TRAIN_BATCH, seq=TRAIN_SEQ)
    else:
        serve_phase(SERVE_ARCH, reduced=False, batch=SERVE_BATCH,
                    prompt_len=PROMPT_LEN, gen=GEN)
        cache_phase(SERVE_ARCH, reduced=False, batch=CACHE_BATCH,
                    n_tokens=CACHE_TOKENS)
        sim_phase(SIM_LANES, SIM_FAULT_FREE, SIM_SINGLE_CHECKS)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()

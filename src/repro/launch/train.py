"""Training driver: data pipeline → fault-tolerant loop → SPRING collection.

Runs anywhere: on the CPU host it trains reduced configs for real (the
end-to-end example path); on a pod the same code runs under the production
mesh (``--mesh host`` becomes ``--mesh single|multi``).

Example (CPU, ~1 minute):
  PYTHONPATH=src python -m repro.launch.train --arch chatglm3-6b --reduced \\
      --steps 50 --batch 8 --seq 64 --ckpt-dir artifacts/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path
from typing import Any, List

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.core import ProfileCollector
from repro.data.pipeline import DataConfig, Prefetcher
from repro.distributed import (activation_sharding, batch_shardings,
                               default_rules, param_shardings, replicated)
from repro.distributed.fault import (
    FaultTolerantLoop, Heartbeats, PreemptionGuard, ProfilingSupervisor,
    RetryPolicy, Watchdog, retry_with_backoff,
)
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import init_params
from repro.models.api import model_specs, tape_spec
from repro.core.tape import rows_to_stream
from repro.optim import AdamWConfig, init_state, state_specs
from repro.train.step import TrainConfig, make_train_step


@dataclasses.dataclass
class TrainResult:
    losses: List[float]
    grad_norms: List[float]
    params: Any                 # final parameters, placed on ``mesh``
    end_step: int
    collector: ProfileCollector
    supervisor: ProfilingSupervisor
    step_end_s: List[float]     # perf_counter once each step's loss is read


def run_train(
    cfg, *, mesh=None, steps: int = 100, batch: int = 8, seq: int = 64,
    lr: float = 3e-3, grad_accum: int = 1, ckpt_dir=None,
    ckpt_every: int = 25, variant: str = "base", seed: int = 0,
    profile_policy: str = "inline", step_budget_s: float = 30.0,
    trace: bool = False,
) -> TrainResult:
    """Train ``cfg`` for ``steps`` steps on ``mesh`` (default: every
    visible device as one data axis).

    Parameters, optimizer state and batches are placed by the sharding
    rules of ``variant`` as they are made, so weights that span several
    devices (FSDP over ``data``) never land on one.  ``ckpt_dir=None``
    trains without checkpoints.
    """
    if cfg.is_encdec:
        raise SystemExit("use examples/train_lm.py family-specific drivers "
                         "for enc-dec; this driver trains LM families")
    mesh = make_host_mesh() if mesh is None else mesh
    rules = default_rules(variant)

    specs = model_specs(cfg)
    p_shard = param_shardings(specs, mesh, rules)
    o_shard = param_shardings(state_specs(specs), mesh, rules)
    params = init_params(specs, jax.random.PRNGKey(seed), p_shard)
    opt_state = jax.jit(init_state, out_shardings=o_shard)(params)

    tcfg = TrainConfig(
        optimizer=AdamWConfig(lr=lr, warmup_steps=10,
                              total_steps=max(steps, 20)),
        grad_accum=grad_accum)
    step = make_train_step(cfg, tcfg)
    b_shard = batch_shardings(cfg, mesh, rules, {
        k: jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        for k in ("tokens", "labels")})

    def wrapped(params, opt_state, batch):
        with activation_sharding(mesh, rules):
            return step(params, opt_state, batch)

    jit_step = jax.jit(
        wrapped, in_shardings=(p_shard, o_shard, b_shard),
        out_shardings=(p_shard, o_shard, replicated(mesh), replicated(mesh)),
        donate_argnums=(0, 1))

    dcfg = DataConfig(seed=seed + 1, global_batch=batch,
                      seq_len=seq, vocab_size=cfg.vocab_size)
    collector = ProfileCollector()
    if trace:
        collector.attach_trace()
    spec = tape_spec(cfg)
    hb = Heartbeats(n_hosts=1)
    guard = PreemptionGuard()
    supervisor = ProfilingSupervisor(policy=profile_policy)
    watchdog = Watchdog(budget_s=step_budget_s)
    retry = RetryPolicy(retries=2, base_delay=0.02)

    def ingest_rows(rows):
        # host-side decode path: verified, retried, and supervised — a
        # damaged stream quarantines one step's signals, never kills training
        stream = rows_to_stream(spec, rows, layer_prefix="block")
        _, report = retry_with_backoff(
            collector.ingest_verified, stream, policy=retry)
        if not report.ok:
            supervisor.record_integrity_failure(report.summary())
        else:
            supervisor.step_ok()

    def step_fn(state, batch):
        params, opt_state = state
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("train.step"):
            params, opt_state, metrics, rows = jit_step(
                params, opt_state, batch)
        dt = time.perf_counter() - t0
        if supervisor.active and rows is not None and rows.size:
            with jax.profiler.TraceAnnotation("train.profile"):
                t_prof = time.perf_counter()
                ingest_rows(rows)
                if watchdog.observe(dt):
                    supervisor.record_overhead(
                        (time.perf_counter() - t_prof) / max(dt, 1e-9))
        return (params, opt_state), metrics

    loop = FaultTolerantLoop(
        ckpt_dir, (params, opt_state), step_fn, ckpt_every=ckpt_every,
        shardings=(p_shard, o_shard), heartbeat=hb, preemption=guard)

    losses, grad_norms, step_end_s = [], [], []

    def on_metrics(s, m):
        loss = float(m["loss"])   # waits for the step on the device
        step_end_s.append(time.perf_counter())
        losses.append(loss)
        grad_norms.append(float(m["grad_norm"]))
        # persistent stragglers starve the profile drain: fold them into
        # the same degradation ladder as integrity/overhead strikes
        supervisor.observe_heartbeats(hb)
        if s % 10 == 0 or s == loop.start_step:
            strag = hb.stragglers()
            print(f"step {s:5d} loss {loss:8.4f} "
                  f"gnorm {grad_norms[-1]:8.3f} "
                  f"lr {float(m['lr']):.2e}"
                  + (f"  STRAGGLERS: {strag}" if strag else ""))

    prefetch = Prefetcher(dcfg, start_step=loop.start_step)
    try:
        def batches():
            while True:
                _, b = prefetch.get()
                yield b
        end_step = loop.run(batches(), steps, on_metrics=on_metrics)
    finally:
        prefetch.close()
        guard.uninstall()

    print(f"finished at step {end_step}; "
          f"data-queue max fullness = {prefetch.queue_fullness} "
          f"(SPRING host FIFO signal)")
    return TrainResult(losses=losses, grad_norms=grad_norms,
                       params=loop.state[0], end_step=end_step,
                       collector=collector, supervisor=supervisor,
                       step_end_s=step_end_s)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="chatglm3-6b")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (smoke) config — CPU-friendly")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--mesh", choices=["host", "single", "multi"],
                    default="host")
    ap.add_argument("--variant", default="base")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile-report", default=None,
                    help="write the SPRING profile report here")
    ap.add_argument("--profile-policy",
                    choices=("inline", "shortcut", "off"), default="inline")
    ap.add_argument("--step-budget-s", type=float, default=30.0,
                    help="watchdog wall-clock budget per train step")
    ap.add_argument("--trace-out", default=None,
                    help="write the per-step profile timeline here as "
                         "Perfetto/Chrome-trace JSON")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = (make_host_mesh() if args.mesh == "host"
            else make_production_mesh(multi_pod=(args.mesh == "multi")))
    res = run_train(
        cfg, mesh=mesh, steps=args.steps, batch=args.batch, seq=args.seq,
        lr=args.lr, grad_accum=args.grad_accum, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, variant=args.variant, seed=args.seed,
        profile_policy=args.profile_policy, step_budget_s=args.step_budget_s,
        trace=bool(args.trace_out))

    collector, supervisor = res.collector, res.supervisor
    if supervisor.events or collector.integrity_failures:
        print(supervisor.summary())
    if res.losses:
        print(f"loss: first={res.losses[0]:.4f} last={res.losses[-1]:.4f}")
    if args.profile_report:
        Path(args.profile_report).write_text(collector.report())
        print(f"profile report -> {args.profile_report}")
    if args.trace_out and collector.trace is not None:
        from repro.trace import write_perfetto
        store = collector.trace
        for ev in supervisor.events:
            store.add_marker(
                f"profiling: {ev.from_policy}->{ev.to_policy}",
                detail=ev.reason,
                window=min(ev.step, max(store.n_windows - 1, 0)))
        write_perfetto(store, args.trace_out)
        print(f"perfetto trace -> {args.trace_out}")
    return res.losses


if __name__ == "__main__":
    main()

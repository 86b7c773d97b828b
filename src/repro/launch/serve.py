"""Serving driver: batched prefill + decode with KV-cache occupancy profiling.

Greedy-decodes a batch of prompts with the family-appropriate cache
machinery; the SPRING stream reports per-step cache occupancy and attention
logit maxima.  The profiling path runs under a ``ProfilingSupervisor``: a
watchdog + integrity verification degrade it gracefully (inline → shortcut →
off) on repeated faults while the token path keeps serving.  The default
architecture, chatglm3-6b, fits one 16 GB chip at its published widths.
CPU example (``--reduced`` swaps in the d_model-64 smoke preset):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --reduced \\
      --batch 4 --prompt-len 16 --gen 16
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, ModelConfig, get_config
from repro.core import ProfileCollector, ProfileStream, metrics as M
from repro.distributed.fault import (
    ProfilingSupervisor, RetryPolicy, Watchdog, retry_with_backoff,
)
from repro.models import init_params
from repro.models.api import init_caches, model_specs, prefill_fn
from repro.train.step import make_serve_step


@dataclasses.dataclass
class ServeResult:
    tokens: jnp.ndarray
    collector: ProfileCollector
    supervisor: ProfilingSupervisor
    toks_per_s: float
    compile_s: float      # ahead-of-time compile of the serve step
    step_s: float         # wall seconds per generated step after the first


@functools.partial(jax.jit, static_argnums=0)
def _profile_step(policy: str, pos, max_len) -> ProfileStream:
    """Build this step's profile stream at the supervisor's fidelity rung.

    ``inline`` guards every signal record individually (the faithful
    mechanism); ``shortcut`` emits one fixed-width guarded record (the
    tape-style O(L) path — cheaper, coarser framing).  One compiled program
    per policy: ``pos`` and ``max_len`` are traced, so every step and cache
    length reuses it.
    """
    used = jnp.full((1,), pos + 1)
    occ = M.kv_occupancy(used, max_len)
    position = used.astype(jnp.float32)
    s = ProfileStream.create()
    if policy == "inline":
        s = s.append_guarded("kv/occupancy", "fifo_fullness", occ)
        s = s.append_guarded("kv/position", "position", position)
    else:  # shortcut: one guarded record row
        row = jnp.concatenate([jnp.atleast_1d(occ), position])
        s = s.append_guarded("kv/record", "record_row", row)
    return s


def run_serve(
    arch: str | ModelConfig = "chatglm3-6b", *, reduced: bool = False,
    batch: int = 4,
    prompt_len: int = 16, gen: int = 16, seed: int = 0,
    profile_policy: str = "inline", failure_threshold: int = 2,
    overhead_budget: float = 0.25, step_budget_s: float = 5.0,
    corrupt_every: int = 0, trace: bool = False,
) -> ServeResult:
    """Decode ``gen`` tokens per sequence under profiling supervision.

    ``corrupt_every > 0`` injects a bit flip into every N-th step's profile
    stream (fault-injection hook): the verified decode quarantines the
    damaged record, the supervisor counts the strike, and after
    ``failure_threshold`` consecutive strikes profiling steps down a rung —
    tokens keep flowing throughout.  ``arch`` is a registry id or a
    configuration (e.g. one chip's expert share, ``with_expert_share``).
    """
    cfg = get_config(arch) if isinstance(arch, str) else arch
    if reduced:
        cfg = cfg.reduced()

    specs = model_specs(cfg)
    params = init_params(specs, jax.random.PRNGKey(seed))
    max_len = prompt_len + gen
    caches = init_caches(cfg, batch, max_len)

    prompts = jax.random.randint(
        jax.random.PRNGKey(seed + 1), (batch, prompt_len),
        0, cfg.vocab_size, jnp.int32)

    t0 = time.perf_counter()
    serve_step = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, caches, prompts[:, :1], 0).compile()
    compile_s = time.perf_counter() - t0
    collector = ProfileCollector()
    if trace:
        # kv/occupancy words are [used_positions, cache_len]: the cache is
        # full when the used count reaches max_len
        collector.attach_trace(capacities={"kv/occupancy": max_len})
    supervisor = ProfilingSupervisor(
        policy=profile_policy, failure_threshold=failure_threshold,
        overhead_budget=overhead_budget)
    watchdog = Watchdog(budget_s=step_budget_s)
    retry = RetryPolicy(retries=2, base_delay=0.01)

    # prefill by streaming prompt tokens through the decode path (family-
    # uniform; attention archs could use the fused prefill_fn instead)
    t0 = time.perf_counter()
    for pos in range(prompt_len - 1):
        with jax.profiler.TraceAnnotation("serve.step"):
            nxt, caches, rows = retry_with_backoff(
                serve_step, params, caches, prompts[:, pos:pos + 1], pos,
                policy=retry)
    generated = [prompts]
    tok = prompts[:, -1:]
    t_gen = None
    for step_i, pos in enumerate(range(prompt_len - 1, max_len - 1)):
        if step_i == 1:
            # the first generated step also compiled the profiling path's
            # host-side ops; time the steady state from here
            jax.block_until_ready(tok)
            t_gen = time.perf_counter()
        t_step = time.perf_counter()
        with jax.profiler.TraceAnnotation("serve.step"):
            tok, caches, rows = retry_with_backoff(
                serve_step, params, caches, tok, pos, policy=retry)
        generated.append(tok)  # the data path delivers regardless of faults
        if not supervisor.active:
            continue
        with jax.profiler.TraceAnnotation("serve.profile"):
            t_prof = time.perf_counter()
            with jax.profiler.TraceAnnotation("serve.profile.build"):
                s = _profile_step(supervisor.policy, pos, max_len)
                if corrupt_every and step_i % corrupt_every == 0:
                    s = s.with_bitflip(0)  # in-band fault: payload bit flip
            with jax.profiler.TraceAnnotation("serve.profile.verify"):
                decoded, report = s.decode_verified()
            with jax.profiler.TraceAnnotation("serve.profile.fold"):
                collector.fold_verified(decoded, report)
            if not report.ok:
                supervisor.record_integrity_failure(report.summary())
                continue
            dt_step = time.perf_counter() - t_step
            if watchdog.observe(dt_step):
                supervisor.record_overhead(
                    (time.perf_counter() - t_prof) / max(dt_step, 1e-9))
            else:
                supervisor.step_ok()
    jax.block_until_ready(tok)
    t_end = time.perf_counter()

    if trace and collector.trace is not None:
        for ev in supervisor.events:
            collector.trace.add_marker(
                f"profiling: {ev.from_policy}->{ev.to_policy}",
                detail=ev.reason,
                window=min(ev.step, max(collector.trace.n_windows - 1, 0)))

    out = jnp.concatenate(generated, axis=1)
    return ServeResult(
        tokens=out, collector=collector, supervisor=supervisor,
        toks_per_s=batch * (max_len - 1) / (t_end - t0),
        compile_s=compile_s,
        step_s=(t_end - t_gen) / (gen - 1) if gen > 1 else float("nan"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="chatglm3-6b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the reduced (smoke) config — CPU-friendly")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile-policy", choices=("inline", "shortcut", "off"),
                    default="inline")
    ap.add_argument("--corrupt-every", type=int, default=0,
                    help="fault injection: flip a bit in every N-th step's "
                         "profile stream")
    ap.add_argument("--trace-out", default=None,
                    help="write the decode-loop occupancy timeline here as "
                         "Perfetto/Chrome-trace JSON")
    args = ap.parse_args(argv)

    res = run_serve(
        args.arch, reduced=args.reduced, batch=args.batch,
        prompt_len=args.prompt_len, gen=args.gen, seed=args.seed,
        profile_policy=args.profile_policy,
        corrupt_every=args.corrupt_every, trace=bool(args.trace_out))
    out = res.tokens
    print(f"decoded {out.shape} ({res.toks_per_s:.1f} tok/s host)")
    print(res.supervisor.summary())
    print(res.collector.report())
    if args.trace_out and res.collector.trace is not None:
        from repro.trace import write_perfetto
        write_perfetto(res.collector.trace, args.trace_out)
        print(f"perfetto trace -> {args.trace_out}")
    return out


if __name__ == "__main__":
    main()

"""What the latent-attention MoE readers share: the serve step's device
time by model scope, and the step's counts (``bench/counts_mla_moe.py``).

The scopes are read as ``_scopes`` reads them, from the serve step compiled
again at the traced call's shapes; here the step is compiled for the
configuration the driver serves (the registry's model holding the file's
expert share), which ``_scopes.compiled_step_text`` cannot name.
"""
from __future__ import annotations

import weakref

from bench import counts, counts_mla_moe
from bench.metrics import _scopes, _serve

_memo: dict = {}       # the last trace read (a weak reference) and its ms


def compiled_step_text(records) -> str:
    import jax
    import jax.numpy as jnp

    from bench.drivers.serve_mla_moe import program_config
    from repro.models import init_params
    from repro.models.api import init_caches, model_specs
    from repro.train.step import make_serve_step

    cfg = program_config(records["config"])
    specs = model_specs(cfg)
    params = jax.eval_shape(lambda: init_params(specs,
                                                jax.random.PRNGKey(0)))
    caches = jax.eval_shape(lambda: init_caches(
        cfg, records["batch"], records["prompt_len"] + records["gen"]))
    tokens = jax.ShapeDtypeStruct((records["batch"], 1), jnp.int32)
    return jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, caches, tokens, 0).compile().as_text()


def scope_ms(ctx, scope: str):
    """One scope's device ms per timed step, computed once per trace."""
    if _memo.get("trace", lambda: None)() is not ctx.trace:
        window, steps = _serve.steps(ctx)
        got = None
        if steps:
            scopes = _scopes.instruction_scopes(
                compiled_step_text(ctx.records))
            got = _scopes.scope_ms(ctx.trace, window, steps, scopes)
        _memo.update(trace=weakref.ref(ctx.trace), ms=got)
    got = _memo["ms"]
    return None if got is None else got[scope]


def counted(ctx, part: str):
    """(FLOPs, bytes) of one timed step's ``attn``, ``mlp`` or whole step,
    at the mean position attended."""
    r = ctx.records
    cfg, batch = r["config"], r["batch"]
    pos = counts.mean_decode_positions(r["prompt_len"], r["gen"])
    if part == "attn":
        return counts_mla_moe.mla_step(cfg, batch, pos)
    if part == "mlp":
        return counts_mla_moe.ffn_step(cfg, batch)
    return counts_mla_moe.decode_step(cfg, batch, pos)


def least_s(ctx, part: str) -> float:
    """The least time the chip could take for ``part`` of one step."""
    flops, nbytes = counted(ctx, part)
    return max(flops / ctx.peaks["bf16_flops_per_s"],
               nbytes / ctx.peaks["hbm_bytes_per_s"])


def scope_roofline(ctx, scope: str):
    """``scope``'s least time over its measured device time (%)."""
    ms = scope_ms(ctx, scope)
    if not ms:
        return None
    return 100.0 * least_s(ctx, scope) / (1e-3 * ms)

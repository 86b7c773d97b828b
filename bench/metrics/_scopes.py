"""Device time of the serve step by the model's named scopes.

The program names the parts of its decode step with ``jax.named_scope``:
``attn`` (projections, rotary, scores, and inside it ``kv_update``),
``mlp``, ``norm``, ``embed`` and ``logits``.  XLA keeps the scope path in
each HLO instruction's ``op_name`` metadata, fusions included.  The TPU's
device events carry the instruction's text without that metadata, and
``bench.tracing`` keeps no event stats, so the serve step is compiled
again here at the traced call's shapes (a hit in the persistent
compilation cache).  Each traced operation is found there by its head,
``%name = <result type> <opcode>``, and counted under the first model
scope on its ``op_name`` path.

An operation's own time (``tracing.self_times``) is counted under its
scope for every serve-step execution in the decode window.  Operations
under no model scope (the layer scan's cache stacking and weight-slice
copies, compiler-inserted copies, the loop itself) count as unscoped.  The
readers give None when no instruction of the step carries a model scope (a
program without the scopes), or when a traced operation's head is not in
the compiled program (another program than the one traced).
"""
from __future__ import annotations

import re
import weakref
from typing import Dict, Optional

from bench import tracing
from bench.metrics import _serve

SCOPES = ("attn", "mlp", "norm", "embed", "logits")
UNSCOPED = "unscoped"

_HEAD = re.compile(r'^\s*(?:ROOT\s+)?(%[\w.\-]+ = .*?) ([\w\-]+)\(')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_memo: dict = {}       # the last trace read (a weak reference) and its ms


def scope_of(op_name: str) -> str:
    """The first model scope on an ``op_name`` path, else ``unscoped``."""
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return UNSCOPED


def head(text: str) -> Optional[str]:
    """``%name = <result type> <opcode>`` of an HLO instruction's text."""
    m = _HEAD.match(text)
    return None if m is None else f"{m.group(1)} {m.group(2)}"


def instruction_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction head -> model scope, for every instruction of compiled
    HLO text (those without ``op_name`` metadata are unscoped)."""
    out = {}
    for line in hlo_text.splitlines():
        key = head(line)
        if key is not None:
            m = _OP_NAME.search(line)
            out[key] = scope_of(m.group(1)) if m else UNSCOPED
    return out


def compiled_step_text(records) -> str:
    """The serve step as ``run_serve`` compiles it for the cell's shapes."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import init_params
    from repro.models.api import init_caches, model_specs
    from repro.train.step import make_serve_step

    prog = records["config"]["program"]
    cfg = get_config(prog["arch"])
    if prog.get("reduced"):
        cfg = cfg.reduced()
    specs = model_specs(cfg)
    params = jax.eval_shape(lambda: init_params(specs,
                                                jax.random.PRNGKey(0)))
    caches = jax.eval_shape(lambda: init_caches(
        cfg, records["batch"], records["prompt_len"] + records["gen"]))
    tokens = jax.ShapeDtypeStruct((records["batch"], 1), jnp.int32)
    return jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, caches, tokens, 0).compile().as_text()


def scope_ms(trace, window, steps, scopes: Dict[str, str]
             ) -> Optional[Dict[str, float]]:
    """Own device time per scope and serve-step execution (ms).

    ``steps`` are the serve step's executions in ``window``; operations
    count where they start inside one of them.
    """
    if not steps or not any(s != UNSCOPED for s in scopes.values()):
        return None
    lo, hi = window
    totals = {s: 0.0 for s in SCOPES + (UNSCOPED,)}
    for d in trace.devices.values():
        ops = _within(d.ops, steps)
        heads = {tracing.op_name(e.name): head(e.name) for e in ops}
        for name, ns in tracing.self_times(ops, lo, hi).items():
            scope = scopes.get(heads[name])
            if scope is None:
                return None
            totals[scope] += ns
    return {s: 1e-6 * ns / len(steps) for s, ns in totals.items()}


def _within(ops, steps):
    """The operations that start inside one of the (disjoint) ``steps``."""
    spans = sorted((e.start, e.end) for e in steps)
    out, k = [], 0
    for e in sorted(ops, key=lambda e: e.start):
        while k < len(spans) and spans[k][1] <= e.start:
            k += 1
        if k == len(spans):
            break
        if spans[k][0] <= e.start:
            out.append(e)
    return out


def read(ctx, scope: str) -> Optional[float]:
    """One scope's ms per timed step, computed once per trace."""
    if _memo.get("trace", lambda: None)() is not ctx.trace:
        window, steps = _serve.steps(ctx)
        got = None
        if steps:
            scopes = instruction_scopes(compiled_step_text(ctx.records))
            got = scope_ms(ctx.trace, window, steps, scopes)
        _memo.update(trace=weakref.ref(ctx.trace), ms=got)
    got = _memo["ms"]
    return None if got is None else got[scope]

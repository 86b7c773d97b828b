"""Activation-sharding context.

Model code calls ``shard_act(x, "batch", None, None)`` at dataflow waypoints;
when a mesh + rules context is active these become
``with_sharding_constraint`` hints, otherwise they are identity (CPU tests
never notice).  Keeping it contextual lets the same pure model functions run
single-device and multi-pod unchanged — the distribution layer composes from
the outside, like the profiling stream does.
"""
from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_CTX: contextvars.ContextVar[Optional[Tuple[Mesh, Dict[str, Any]]]] = \
    contextvars.ContextVar("sharding_ctx", default=None)


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, rules: Dict[str, Any]):
    """shard_act() becomes active inside this context (trace-time safe:
    constraints carry explicit NamedShardings, so no jax.set_mesh needed)."""
    token = _CTX.set((mesh, rules))
    try:
        yield
    finally:
        _CTX.reset(token)


def logical_pspec(rules: Dict[str, Any], axes, shape=None,
                  mesh: Optional[Mesh] = None) -> P:
    """Logical axis names -> PartitionSpec, with divisibility fallback."""
    parts = []
    used = set()
    for i, ax in enumerate(axes):
        target = rules.get(ax) if ax else None
        if target is None:
            parts.append(None)
            continue
        names = (target,) if isinstance(target, str) else tuple(target)
        if mesh is not None:
            names = tuple(n for n in names if n in mesh.shape and n not in used)
            size = math.prod(mesh.shape[n] for n in names) if names else 1
            if shape is not None and (not names or shape[i] % size != 0):
                parts.append(None)
                continue
            used.update(names)
        parts.append(names[0] if len(names) == 1 else (names or None))
    return P(*parts)


def shard_act(x, *axes):
    """Constrain activation ``x`` to the logical ``axes`` if a mesh is active."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = logical_pspec(rules, axes, shape=x.shape, mesh=mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def per_batch_shard(fn, batch: int, in_batch, out_batch):
    """``fn`` run on each device's share of the batch when a mesh is
    active, else ``fn`` itself.  A Pallas TPU kernel has no partitioning
    rule: the compiler refuses one whose operands are sharded.
    ``in_batch`` and ``out_batch`` give each argument's and result's batch
    dimension (None: replicated); a batch of ``batch`` rows splits as the
    ``"batch"`` rule maps it onto the mesh, as ``shard_act`` would
    constrain it."""
    ctx = _CTX.get()
    if ctx is None:
        return fn
    mesh, rules = ctx
    names = logical_pspec(rules, ("batch",), shape=(batch,), mesh=mesh)[0]

    def specs(dims):
        return tuple(P() if d is None else P(*[None] * d, names)
                     for d in dims)

    return jax.shard_map(fn, mesh=mesh, in_specs=specs(in_batch),
                         out_specs=specs(out_batch), check_vma=False)

"""Parameter specification system.

Every model declares its parameters as a nested dict of :class:`ParamSpec`
leaves.  From one spec tree we derive:

  * concrete initialization (``init_params``) for real runs;
  * abstract ``ShapeDtypeStruct`` trees (``abstract_params``) for the
    multi-pod dry-run — no allocation, exactly like shannon/kernels'
    input-spec pattern;
  * per-parameter ``NamedSharding`` from logical axis names + a rules table
    (``shardings_for``), with automatic divisibility fallback (axes that
    don't divide the mesh dimension are replicated rather than crashing —
    e.g. MQA's single KV head on a 16-way model axis).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = Tuple[Optional[str], ...]


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    dtype: Any = jnp.bfloat16
    axes: Axes = ()                 # logical axis name per dim (None = replicated)
    init: str = "normal"            # normal | zeros | ones | scaled
    scale: Optional[float] = None   # stddev override

    def __post_init__(self):
        if self.axes and len(self.axes) != len(self.shape):
            raise ValueError(f"axes {self.axes} rank != shape {self.shape}")

    @property
    def fan_in(self) -> int:
        return self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]


def _init_leaf(spec: ParamSpec, key) -> jnp.ndarray:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    std = spec.scale if spec.scale is not None else 1.0 / math.sqrt(max(1, spec.fan_in))
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(spec.dtype)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


_init_leaf_jit = jax.jit(_init_leaf, static_argnums=0)


def init_params(specs, key, shardings=None):
    """Concrete init: one fresh key per leaf, deterministic in tree order.

    Each leaf is made by its own jitted program, so its float32 draw fuses
    into the cast and no float32 copy of a whole leaf is ever live (eager
    draws held two at once: more than one chip has for the largest leaf of
    a 6B model).  ``shardings`` (a tree from :func:`shardings_for`) places
    every leaf where it is made, so a model larger than one device never
    lands on one.
    """
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    if shardings is None:
        inits = [_init_leaf_jit] * len(leaves)
    else:
        inits = [jax.jit(_init_leaf, static_argnums=0, out_shardings=sh)
                 for sh in treedef.flatten_up_to(shardings)]
    vals = [init(s, k) for init, s, k in zip(inits, leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def abstract_params(specs):
    """ShapeDtypeStruct tree — the dry-run stand-in (no allocation)."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs, is_leaf=is_spec
    )


def spec_pspec(spec: ParamSpec, rules: Dict[str, Any], mesh: Mesh) -> P:
    """Logical axes -> PartitionSpec under ``rules`` with divisibility checks."""
    parts = []
    used = set()
    for dim, ax in zip(spec.shape, spec.axes or (None,) * len(spec.shape)):
        target = rules.get(ax) if ax else None
        if target is None:
            parts.append(None)
            continue
        names = (target,) if isinstance(target, str) else tuple(target)
        names = tuple(n for n in names if n in mesh.shape and n not in used)
        size = math.prod(mesh.shape[n] for n in names) if names else 1
        if not names or dim % size != 0:
            parts.append(None)       # fallback: replicate this dim
            continue
        used.update(names)
        parts.append(names[0] if len(names) == 1 else names)
    return P(*parts)


def shardings_for(specs, mesh: Mesh, rules: Dict[str, Any]):
    """NamedSharding tree for a spec tree (params placement / in_shardings)."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, spec_pspec(s, rules, mesh)),
        specs, is_leaf=is_spec,
    )


def count_params(specs) -> int:
    leaves = jax.tree_util.tree_leaves(specs, is_leaf=is_spec)
    return sum(math.prod(s.shape) for s in leaves)


def param_bytes(specs) -> int:
    leaves = jax.tree_util.tree_leaves(specs, is_leaf=is_spec)
    return sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize for s in leaves)

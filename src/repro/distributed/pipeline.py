"""GPipe-style pipeline parallelism over a mesh axis.

Completes the parallelism menu (DP/TP/EP/SP live in sharding.py): layers
are split into S contiguous stages laid out along a mesh axis; microbatches
stream through with ``jax.lax.ppermute`` forwarding activations stage→stage
each tick.  A full forward takes ``n_micro + n_stages − 1`` ticks, i.e.
pipeline utilization = n_micro / (n_micro + S − 1) — the bubble the roofline
model charges when the pod axis is used as a stage axis.

The schedule runs inside ``shard_map`` (explicit collectives), composes
with data parallelism on the other mesh axes, and is validated numerically
against the unpipelined layer stack in tests/test_pipeline.py.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_stage_fn(block_fn: Callable, n_stages: int, stage_axis: str):
    """Builds the per-device pipelined forward (call under shard_map).

    block_fn(stage_params, x) -> x applies ONE stage's layers.

    Args (inside shard_map, per device):
      stage_params: this stage's parameter slice (leading stage dim of 1).
      xs: [n_micro, mb, ...] all microbatches (only stage 0 reads them).
    Returns [n_micro, mb, ...] outputs (only stage S-1's are real).
    """

    def pipelined(stage_params, xs):
        idx = jax.lax.axis_index(stage_axis)
        n_micro = xs.shape[0]
        ticks = n_micro + n_stages - 1
        mb_shape = xs.shape[1:]

        def tick(carry, t):
            recv, outs = carry
            # stage 0 injects microbatch t (or zeros past the end)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inject = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0,
                                                  keepdims=False)
            state = jnp.where(idx == 0, inject, recv)
            out = block_fn(stage_params, state)
            # last stage writes its completed microbatch o_idx = t-(S-1)
            o_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            write = (idx == n_stages - 1) & (t >= n_stages - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, o_idx, 0, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(write, out, cur), o_idx, 0)
            # forward activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            recv = jax.lax.ppermute(out, stage_axis, perm)
            return (recv, outs), None

        recv0 = jnp.zeros(mb_shape, xs.dtype)
        outs0 = jnp.zeros_like(xs)
        (recv, outs), _ = jax.lax.scan(
            tick, (recv0, outs0), jnp.arange(ticks))
        # every device returns outs; only the last stage's are meaningful —
        # broadcast them via a masked psum so the out_spec can be
        # replicated over the stage axis.
        mask = (idx == n_stages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, stage_axis)

    return pipelined


def make_pipelined_forward(block_fn: Callable, mesh: Mesh, stage_axis: str,
                           param_spec: P, x_spec: P):
    """shard_map-wrapped pipelined forward.

    stage_params: [S, ...] stacked per-stage params (sharded on stage_axis);
    xs: [n_micro, mb, ...] microbatches (replicated over stage_axis).
    """
    n_stages = mesh.shape[stage_axis]

    def per_device(stage_params, xs):
        sp = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        return pipeline_stage_fn(block_fn, n_stages, stage_axis)(sp, xs)

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(param_spec, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )


def pipeline_utilization(n_micro: int, n_stages: int) -> float:
    """GPipe bubble model: useful ticks / total ticks."""
    return n_micro / (n_micro + n_stages - 1)

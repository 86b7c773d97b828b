"""Shared model components: norms, rotary embeddings, activation helpers."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm: fp32 reduction, native-dtype application.

    Only the (tiny) mean-square reduction runs in fp32; the full-width
    multiply stays in the input dtype, so no f32 copy of the activation
    tensor round-trips HBM (§Perf H5 — the f32-conversion chains were the
    largest single memory term in the remat backward).
    """
    with jax.named_scope("norm"):
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        scale = jax.lax.rsqrt(var + eps).astype(x.dtype)
        return x * scale * weight.astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def rotary_angles(positions: jnp.ndarray, dim: int, theta: float) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for ``dim`` rotary features at integer ``positions``."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., dim/2]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(
    x: jnp.ndarray,            # [..., T, H, Dh]
    positions: jnp.ndarray,    # [..., T]
    theta: float = 1e4,
    rotary_fraction: float = 1.0,
) -> jnp.ndarray:
    """RoPE on the leading ``rotary_fraction`` of head dims.

    ``rotary_fraction=0.5`` gives ChatGLM's "2d" RoPE layout: the first half
    of each head rotates with position, the second half passes through.
    """
    dh = x.shape[-1]
    rot = int(dh * rotary_fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    cos, sin = rotary_angles(positions, rot, theta)     # [..., T, rot/2]
    cos = cos[..., None, :]                              # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = xr[..., 0::2].astype(jnp.float32), xr[..., 1::2].astype(jnp.float32)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([yr, xp], axis=-1) if rot < dh else yr


def write_in_place(stack, update, index):
    """``stack`` with ``update`` written at ``index``, held in row-major
    order: the write of a cache that a layer loop carries.

    Left to choose, the TPU compiler lays a loop-carried buffer out for the
    loop's own writes and reads, and copies the whole cache into and out of
    that layout around the loop.  Held row-major, the loop keeps the layout
    the device gives the donated cache, which is row-major when the cache's
    two minor dimensions are whole (8, 128) tiles and the minor one is a
    multiple of 128 wide (``kv_cache_init`` shapes the caches so), and the
    write touches only ``update``'s tiles.
    """
    out = jax.lax.dynamic_update_slice(stack, update.astype(stack.dtype),
                                       index)
    return with_layout_constraint(out, Layout(tuple(range(out.ndim))))


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


ACTIVATIONS = {"silu": silu, "gelu": gelu, "relu": jax.nn.relu}


def causal_mask_bias(q_len: int, kv_len: int, q_offset) -> jnp.ndarray:
    """Additive causal bias [q_len, kv_len]; q position i attends kv <= offset+i."""
    q_pos = q_offset + jnp.arange(q_len)[:, None]
    kv_pos = jnp.arange(kv_len)[None, :]
    return jnp.where(kv_pos <= q_pos, 0.0, -1e30).astype(jnp.float32)

"""Pure-jnp oracles for every Pallas kernel (the CoSim of the kernel layer)."""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp


def mha_reference(q, k, v, *, causal: bool = True):
    """[B, H, T, D] x [B, H, S, D] -> [B, H, T, D], plus logit max."""
    d = q.shape[-1]
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(d)
    if causal:
        t, kv = s.shape[-2], s.shape[-1]
        mask = jnp.arange(kv)[None, :] <= jnp.arange(t)[:, None]
        s = jnp.where(mask, s, -1e30)
    lmax = jnp.max(s)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhts,bhsd->bhtd", w, v.astype(jnp.float32))
    return out.astype(q.dtype), lmax


def block_logit_max_reference(q, k, *, causal: bool, q_block: int):
    """Per-(head, q_block) max logit — oracle for the in-band profile."""
    B, H, T, D = q.shape
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(D)
    if causal:
        kv = s.shape[-1]
        mask = jnp.arange(kv)[None, :] <= jnp.arange(T)[:, None]
        s = jnp.where(mask, s, -1e30)
    n_q = T // q_block
    s = s.reshape(B, H, n_q, q_block, -1)
    return jnp.max(s, axis=(3, 4))


def moe_dispatch_reference(eids: jnp.ndarray, n_experts: int, capacity: int):
    """Arrival-order slot assignment + counts/fullness/overflow."""
    M = eids.shape[0]
    onehot = jax.nn.one_hot(eids, n_experts, dtype=jnp.int32)     # [M, E]
    within = jnp.cumsum(onehot, axis=0) - onehot                  # exclusive
    slots = jnp.sum(within * onehot, axis=1)
    counts = jnp.sum(onehot, axis=0)
    fullness = jnp.minimum(counts, capacity).astype(jnp.float32)
    overflow = jnp.maximum(counts - capacity, 0).astype(jnp.float32)
    return slots, counts, fullness, overflow


def ssd_state_passing_reference(states, decays):
    """[B, NC, H, P, N], [B, NC, H] -> states BEFORE each chunk."""
    def body(carry, inp):
        s_c, dec = inp
        out = carry
        carry = dec[:, :, None, None] * carry + s_c
        return carry, out

    B, NC, H, P, N = states.shape
    s0 = jnp.zeros((B, H, P, N), jnp.float32)
    _, outs = jax.lax.scan(
        body, s0, (states.transpose(1, 0, 2, 3, 4).astype(jnp.float32),
                   decays.transpose(1, 0, 2).astype(jnp.float32)))
    return outs.transpose(1, 0, 2, 3, 4)


def matmul_reference(a, b) -> Tuple[jnp.ndarray, jnp.ndarray]:
    out = (a.astype(jnp.float32) @ b.astype(jnp.float32))
    return out.astype(a.dtype), out


def tile_absmax_reference(a, b, block_m: int, block_n: int):
    out = a.astype(jnp.float32) @ b.astype(jnp.float32)
    M, N = out.shape
    tiles = out.reshape(M // block_m, block_m, N // block_n, block_n)
    return jnp.max(jnp.abs(tiles), axis=(1, 3))


def mla_decode_reference(q, caches, layer, pos, *, values: int,
                         scale: float):
    """Absorbed decode attention over layer ``layer`` of the stacked latent
    caches, sliced and narrowed to the query's width, with every position
    scored and those past ``pos`` masked: (out [B, H, values], largest
    scaled logit per sequence and head [B, H])."""
    cache = jax.lax.dynamic_index_in_dim(caches, layer, keepdims=False)
    cache = cache[..., :q.shape[-1]]                         # [B, S, c]
    logits = jnp.einsum("bhc,bsc->bhs", q.astype(cache.dtype), cache,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(cache.shape[1]) < pos + 1
    logits = jnp.where(valid, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(cache.dtype)
    out = jnp.einsum("bhs,bsr->bhr", w, cache[..., :values])
    return out, jnp.max(logits, axis=-1)

"""Pallas TPU flash attention (causal, GQA-ready) with in-band profiling.

Target: TPU MXU/VMEM.  Grid = (batch·kv_heads·q_groups, q_blocks); each
program instance streams KV blocks of its causal prefix through VMEM with a
``fori_loop``, keeping the online-softmax state (m, l, acc) in registers/
VMEM.  Block shapes are BlockSpec-tiled so the working set
(q_blk·d + 2·kv_blk·d + q_blk·kv_blk) fits VMEM, with MXU-aligned (128)
tiles.

SPRING twist: the kernel optionally emits an in-band profile record per
(head, q_block) — the running max logit — into a third output buffer that
rides along with the attention output, exactly like the paper's profiling
stream rides the data stream (no separate extraction pass over the scores).

Validated in interpret mode against ``ref.mha_reference`` (CPU has no MXU;
interpret=True executes the same program in Python).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, prof_ref, *, kv_blk: int,
                      scale: float, causal: bool, profile: bool):
    """One (q_block × all kv_blocks) pass.  Shapes (per block):
    q_ref [q_blk, d]; k_ref/v_ref [S, d]; o_ref [q_blk, d]; prof_ref [1, n_q]."""
    q_blk, d = q_ref.shape
    S = k_ref.shape[0]
    qi = pl.program_id(1)
    q0 = qi * q_blk

    q = q_ref[...].astype(jnp.float32) * scale

    n_kv = S // kv_blk
    if causal:
        # only stream blocks in the causal prefix of this q block
        n_kv_live = (q0 + q_blk + kv_blk - 1) // kv_blk
    else:
        n_kv_live = n_kv

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * kv_blk, kv_blk), :]
        v = v_ref[pl.ds(j * kv_blk, kv_blk), :]
        s = q @ k.astype(jnp.float32).T                     # [q_blk, kv_blk]
        if causal:
            q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kv_pos = j * kv_blk + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(kv_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + p @ v.astype(jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((q_blk,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((q_blk,), jnp.float32)
    acc0 = jnp.zeros((q_blk, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kv_live, body, (m0, l0, acc0))

    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    if profile:
        # in-band record: running max logit of this (head, q_block)
        # (the [1, n_q] row is revisited by every q block of this head;
        # each block fills only its own lane)
        lane = jax.lax.broadcasted_iota(jnp.int32, prof_ref.shape, 1)
        prof_ref[...] = jnp.where(lane == qi, jnp.max(m), prof_ref[...])


def flash_attention(
    q: jnp.ndarray,          # [B, H, T, D]
    k: jnp.ndarray,          # [B, H, S, D]  (KV heads already broadcast)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_block: int = 128,
    kv_block: int = 128,
    profile: bool = True,
    interpret: bool = False,
):
    """Returns (out [B, H, T, D], profile [B, H, n_q_blocks] or None)."""
    B, H, T, D = q.shape
    S = k.shape[2]
    q_blk = min(q_block, T)
    kv_blk = min(kv_block, S)
    if T % q_blk or S % kv_blk:
        raise ValueError(f"T={T}/S={S} must divide blocks {q_blk}/{kv_blk}")
    n_q = T // q_blk
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _flash_fwd_kernel, kv_blk=kv_blk, scale=scale, causal=causal,
        profile=profile)

    out, prof = pl.pallas_call(
        kernel,
        grid=(B * H, n_q),
        in_specs=[
            pl.BlockSpec((None, q_blk, D), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, S, D), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((None, S, D), lambda h, i: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, q_blk, D), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, 1, n_q), lambda h, i: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, n_q), jnp.float32),
        ],
        interpret=interpret,
    )(q.reshape(B * H, T, D), k.reshape(B * H, S, D), v.reshape(B * H, S, D))

    out = out.reshape(B, H, T, D)
    return (out, prof.reshape(B, H, n_q)) if profile else (out, None)

"""Pallas TPU kernel: blocked matmul with an in-band profile epilogue.

This is Listing 1 of the paper transplanted into a TPU kernel: the hot
datapath op computes its result AND appends its locally collected profile
words (running absmax of the output tile — the numerical-health analogue of
``max_depth``) to a profile output that rides alongside, instead of
requiring a separate pass over the output tensor.

Grid (m_blocks, n_blocks, k_blocks); K is innermost/sequential so the fp32
accumulator tile lives in VMEM scratch across the K walk.  Block shapes are
MXU-aligned multiples of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, prof_ref, acc_ref, *, n_k: int,
                   profile: bool):
    j, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...].astype(jnp.float32), b_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...]
        o_ref[...] = acc.astype(o_ref.dtype)
        if profile:
            # in-band profile word: absmax of this output tile, written
            # into lane j of the [1, N/bn] row that every tile of row
            # block i revisits
            lane = jax.lax.broadcasted_iota(jnp.int32, prof_ref.shape, 1)
            prof_ref[...] = jnp.where(lane == j, jnp.max(jnp.abs(acc)),
                                      prof_ref[...])


def profiled_matmul(
    a: jnp.ndarray,          # [M, K]
    b: jnp.ndarray,          # [K, N]
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    profile: bool = True,
    interpret: bool = False,
):
    """Returns (a @ b, tile_absmax [M/bm, N/bn])."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    if M % bm or N % bn or K % bk:
        raise ValueError(f"dims {(M, K, N)} must divide blocks {(bm, bk, bn)}")
    n_k = K // bk

    kernel = functools.partial(_matmul_kernel, n_k=n_k, profile=profile)
    out, prof = pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((None, 1, N // bn), lambda i, j, k: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), a.dtype),
            jax.ShapeDtypeStruct((M // bm, 1, N // bn), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
    return out, (prof.reshape(M // bm, N // bn) if profile else None)

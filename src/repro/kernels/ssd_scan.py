"""Pallas TPU kernel: Mamba2 SSD inter-chunk state passing.

The chunked SSD formulation (models/ssm.py) reduces the sequential part of
the recurrence to a tiny scan over per-chunk states:

    out[c]   = S_running            (state BEFORE chunk c)
    S_running = decay[c] * S_running + S[c]

This kernel runs that recurrence on-chip: grid = (batch, head_blocks); each
instance keeps its [HB, P, N] running state in VMEM across the sequential
chunk walk (chunks = the innermost, revisited block dimension), so the
states stream through HBM exactly once in, once out.

Validated in interpret mode against ``ref.ssd_state_passing_reference``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _state_passing_kernel(s_ref, decay_ref, out_ref, carry_ref, *,
                          n_chunks: int):
    """Blocks: s_ref [1, HB, P, N] (chunk c), decay_ref [NC, HB] (SMEM,
    every chunk's decays), out_ref [1, HB, P, N], carry_ref (scratch)
    [HB, P, N]."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    running = carry_ref[...]
    out_ref[0] = running.astype(out_ref.dtype)
    for h in range(carry_ref.shape[0]):                  # scalar decay per head
        carry_ref[h] = (decay_ref[c, h] * running[h]
                        + s_ref[0, h].astype(jnp.float32))


def ssd_state_passing(
    states: jnp.ndarray,     # [B, NC, H, P, N] per-chunk states
    decays: jnp.ndarray,     # [B, NC, H] per-chunk decay factors
    *,
    head_block: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns states BEFORE each chunk: [B, NC, H, P, N] (exclusive scan)."""
    B, NC, H, P, N = states.shape
    hb = min(head_block, H)
    if H % hb:
        raise ValueError(f"H={H} must divide head_block={hb}")

    kernel = functools.partial(_state_passing_kernel, n_chunks=NC)

    # layout: [B*Hblocks, NC, HB, P, N] so the chunk walk is the revisited
    # (sequential) grid dimension and heads parallelize.
    s = states.transpose(0, 2, 1, 3, 4).reshape(B * (H // hb), hb, NC, P, N)
    s = s.transpose(0, 2, 1, 3, 4)                       # [BH, NC, HB, P, N]
    d = decays.transpose(0, 2, 1).reshape(B * (H // hb), hb, NC)
    d = d.transpose(0, 2, 1)                             # [BH, NC, HB]

    out = pl.pallas_call(
        kernel,
        grid=(B * (H // hb), NC),
        in_specs=[
            pl.BlockSpec((None, 1, hb, P, N), lambda b, c: (b, c, 0, 0, 0)),
            pl.BlockSpec((None, NC, hb), lambda b, c: (b, 0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((None, 1, hb, P, N),
                               lambda b, c: (b, c, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * (H // hb), NC, hb, P, N),
                                       jnp.float32),
        # persistent VMEM carry across the sequential chunk dimension
        scratch_shapes=[pltpu.VMEM((hb, P, N), jnp.float32)],
        interpret=interpret,
    )(s, d)
    out = out.transpose(0, 2, 1, 3, 4).reshape(B, H, NC, P, N)
    return out.transpose(0, 2, 1, 3, 4)

"""Pallas TPU kernel: latent-attention decode over the position blocks in use.

One decode token per sequence attends, with the absorbed query, to one
layer of MLA's latent caches stacked over layers, ``[L, B, S, row]``: each
position's ``[c_kv | k_pe]``, padded to ``row`` lanes.  The kernel takes the
whole stacked buffer, as the layer scan carries it; ``layer`` and ``pos``
are scalar-prefetch operands, so the cache's index map picks the layer
itself and no per-layer slice of the cache is ever materialised.

Grid = (batch blocks, position blocks of 128, or one of ``S`` below that).
The position index map clamps to the last block holding ``pos``: a
repeated block index issues no DMA, so the blocks past ``pos`` are never
fetched, and ``pl.when`` skips their compute.  A step reads
``pos // blk + 1`` of the ``ceil(S / blk)`` blocks.  Where ``blk`` does not
divide ``S`` the last block runs past the cache; only the block holding
``pos`` is masked, rows and scores, so what lies past ``pos`` (or past the
cache) is never weighed.

Rows are read whole.  The query is zero-padded to ``row``, so the padding
lanes add nothing to the scores; the value sum runs over the row and its
first ``values`` lanes are kept.  That is exact while the padding is finite,
which it is: the caches start as zeros and every write leaves the padding
alone.

The softmax is online (running max, running sum, float32 accumulator), and
the running maxima are returned with the output: the in-band record of the
largest logit comes out of the kernel's own state, with no second pass over
the scores.

Validated in interpret mode against ``ref.mla_decode_reference``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
POSITION_BLOCK = 128
BATCH_BLOCK = 16


def _position_block(max_len: int) -> int:
    return min(POSITION_BLOCK, max_len)


def _batch_block(batch: int) -> int:
    return next(b for b in range(min(BATCH_BLOCK, batch), 0, -1)
                if batch % b == 0)


def cache_block(i, j, scalars, *, blk: int):
    """Index map of the cache: layer ``scalars[0]``, batch block ``i``, and
    position block ``j`` clamped to the last block holding ``scalars[1]``."""
    return scalars[0], i, jnp.minimum(j, scalars[1] // blk), 0


def _kernel(scalars, q_ref, c_ref, o_ref, m_ref, m_sc, l_sc, acc_sc, *,
            blk: int, scale: float):
    """q_ref [bb, H, row]; c_ref [bb, blk, row]; o_ref [bb, H, values];
    m_ref, m_sc, l_sc [bb, H, 1]; acc_sc [bb, H, row]."""
    j = pl.program_id(1)
    pos = scalars[1]
    last = pos // blk

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def accumulate(kv, s):
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = alpha * acc_sc[...] + jnp.einsum(
            "bhs,bsc->bhc", p.astype(kv.dtype), kv,
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    def scores(kv):
        return jnp.einsum("bhc,bsc->bhs", q_ref[...], kv,
                          preferred_element_type=jnp.float32) * scale

    @pl.when(j < last)
    def _whole_block():
        kv = c_ref[...]
        accumulate(kv, scores(kv))

    @pl.when(j == last)
    def _last_block():
        kv = c_ref[...]
        at = j * blk + jax.lax.broadcasted_iota(jnp.int32, kv.shape, 1)
        kv = jnp.where(at <= pos, kv, jnp.zeros_like(kv))
        s = scores(kv)
        at = j * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        accumulate(kv, jnp.where(at <= pos, s, NEG_INF))
        acc = acc_sc[...] / l_sc[...]
        o_ref[...] = acc[..., :o_ref.shape[-1]].astype(o_ref.dtype)
        m_ref[...] = m_sc[...]


def mla_decode(q, caches, layer, pos, *, values: int, scale: float,
               interpret: bool | None = None):
    """Absorbed decode attention of one token a sequence.

    q: [B, H, c], the absorbed query ``[q_nope W_UK | q_pe]`` (c <= row);
    caches: [L, B, S, row]; ``layer`` and ``pos`` scalars: attend to
    positions ``0..pos`` of layer ``layer``.  Returns (the softmax-weighted
    sum of the first ``values`` lanes of the rows [B, H, values] in the
    cache's dtype, the largest scaled logit of each sequence and head
    [B, H] in float32).

    ``interpret=None`` lowers the Mosaic kernel where the program is
    lowered for the TPU and the interpreter elsewhere, whatever the
    process's default backend (an ahead-of-time compile for a described
    TPU gets the kernel).
    """
    if interpret is None:
        call = functools.partial(mla_decode, values=values, scale=scale)
        return jax.lax.platform_dependent(
            q, caches, layer, pos,
            tpu=functools.partial(call, interpret=False),
            default=functools.partial(call, interpret=True))
    B, H, c = q.shape
    _, _, S, row = caches.shape
    blk, bb = _position_block(S), _batch_block(B)
    q = jnp.pad(q.astype(caches.dtype), ((0, 0), (0, 0), (0, row - c)))
    scalars = jnp.stack([jnp.asarray(layer, jnp.int32),
                         jnp.asarray(pos, jnp.int32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B // bb, pl.cdiv(S, blk)),
        in_specs=[
            pl.BlockSpec((bb, H, row), lambda i, j, s: (i, 0, 0)),
            pl.BlockSpec((None, bb, blk, row),
                         functools.partial(cache_block, blk=blk)),
        ],
        out_specs=[
            pl.BlockSpec((bb, H, values), lambda i, j, s: (i, 0, 0)),
            pl.BlockSpec((bb, H, 1), lambda i, j, s: (i, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((bb, H, 1), jnp.float32),
                        pltpu.VMEM((bb, H, 1), jnp.float32),
                        pltpu.VMEM((bb, H, row), jnp.float32)],
    )
    out, m = pl.pallas_call(
        functools.partial(_kernel, blk=blk, scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, values), caches.dtype),
                   jax.ShapeDtypeStruct((B, H, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mla_decode",
    )(scalars, q, caches)
    return out, m[..., 0]

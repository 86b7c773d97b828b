"""Pallas TPU kernel: MoE token→expert binning with FIFO-fullness profiling.

The binning step of MoE dispatch — for every (token, k) assignment compute
its *slot* in the target expert's capacity buffer, plus per-expert counts —
is the part that doesn't map onto dense matmul.  On GPU this is atomics; the
TPU-native adaptation processes experts in blocks: for each expert block the
kernel streams the assignment vector through VMEM and computes a masked
running prefix count, which yields both slots and final counts without
atomics (deterministic, sorted-equivalent order).

SPRING tie-in: per-expert fullness (count saturated at capacity) and
overflow (count − capacity) are emitted as a profile output alongside the
slots — the paper's FIFO-fullness metric measured *inside* the hot kernel,
in-band.

Grid: (n_expert_blocks,).  Each instance owns EB experts and scans the
full assignment vector as [M/TB, TB] tiles (VMEM working set EB×TB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _dispatch_kernel(eids_ref, slots_ref, counts_ref, fullness_ref,
                     overflow_ref, *, expert_blk: int, tok_blk: int,
                     capacity: int):
    n_tiles = eids_ref.shape[0]
    eb = pl.program_id(0)
    e0 = eb * expert_blk
    experts = e0 + jax.lax.broadcasted_iota(jnp.int32, (expert_blk, 1), 0)
    first_block = eb == 0          # hoisted: program_id isn't legal in-loop

    upper = (jax.lax.broadcasted_iota(jnp.int32, (tok_blk, tok_blk), 0)
             < jax.lax.broadcasted_iota(jnp.int32, (tok_blk, tok_blk), 1)
             ).astype(jnp.float32)

    def body(t, carry):
        running = carry                                    # [EB, 1]
        ids = eids_ref[pl.ds(t, 1), :]                     # [1, TB]
        match = ids == experts                             # [EB, TB]
        # slot of each match = running count + exclusive prefix count within
        # the tile, as a matmul with the strictly upper triangular ones
        # (exact in f32 for tile sizes < 2**24)
        within = jnp.dot(match.astype(jnp.float32), upper,
                         preferred_element_type=jnp.float32).astype(jnp.int32)
        slot_tile = jnp.where(match, running + within, -1)
        # a token matches at most one expert row in this block
        slots_out = jnp.max(slot_tile, axis=0, keepdims=True)  # [1, TB]
        prev = slots_ref[pl.ds(t, 1), :]
        # first expert block initializes the (revisited) output buffer
        prev = jnp.where(first_block, -1, prev)
        slots_ref[pl.ds(t, 1), :] = jnp.maximum(prev, slots_out)
        running = running + jnp.sum(
            match.astype(jnp.int32), axis=1, keepdims=True)
        return running

    running = jax.lax.fori_loop(
        0, n_tiles, body, jnp.zeros((expert_blk, 1), jnp.int32))
    counts_ref[...] = running
    fullness_ref[...] = jnp.minimum(running, capacity).astype(jnp.float32)
    overflow_ref[...] = jnp.maximum(
        running - capacity, 0).astype(jnp.float32)


def moe_dispatch(
    eids: jnp.ndarray,       # [M] int32 expert assignment per (token, k)
    n_experts: int,
    capacity: int,
    *,
    expert_block: int = 8,
    tok_block: int = 256,
    interpret: bool = False,
):
    """Returns (slots [M], counts [E], fullness [E], overflow [E]).

    ``slots[i]`` is the arrival rank of assignment ``i`` in its expert's
    buffer (drop if >= capacity) — deterministic arrival order, matching the
    sorted-dispatch reference semantics.
    """
    M = eids.shape[0]
    eb = min(expert_block, n_experts)
    tb = min(tok_block, M)
    if n_experts % eb or M % tb:
        raise ValueError(f"E={n_experts}, M={M} must divide blocks {eb}/{tb}")

    kernel = functools.partial(
        _dispatch_kernel, expert_blk=eb, tok_blk=tb, capacity=capacity)

    # eids/slots are tiled [M/TB, TB] so the kernel walks token tiles by
    # row.  The slots block is revisited by every expert block: the first
    # initialises it to -1, later ones merge their matches in with max.
    n_tiles = M // tb
    slots, counts, fullness, overflow = pl.pallas_call(
        kernel,
        grid=(n_experts // eb,),
        in_specs=[pl.BlockSpec((n_tiles, tb), lambda e: (0, 0))],
        out_specs=[
            pl.BlockSpec((n_tiles, tb), lambda e: (0, 0)),
            pl.BlockSpec((eb, 1), lambda e: (e, 0)),
            pl.BlockSpec((eb, 1), lambda e: (e, 0)),
            pl.BlockSpec((eb, 1), lambda e: (e, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles, tb), jnp.int32),
            jax.ShapeDtypeStruct((n_experts, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_experts, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_experts, 1), jnp.float32),
        ],
        interpret=interpret,
    )(eids.reshape(n_tiles, tb))
    return (slots.reshape(M), counts[:, 0], fullness[:, 0], overflow[:, 0])

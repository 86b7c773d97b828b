"""Train / serve step factories.

``make_train_step(cfg)`` returns a pure ``(params, opt_state, batch) ->
(params, opt_state, metrics, profile_rows)`` function: forward (+ SPRING
tape), backward, gradient clipping, AdamW.  Optional microbatch gradient
accumulation (scan) and int8 error-feedback gradient compression (the
distributed-optimization lever for cross-pod all-reduces) hang off the
config.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..models.api import decode_fn, loss_fn
from ..optim import AdamWConfig, AdamWState, apply_updates


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: AdamWConfig = AdamWConfig()
    grad_accum: int = 1                 # microbatches per step (scan)
    compress_grads: bool = False        # int8 error-feedback all-reduce payload


def _split_microbatches(batch: Dict[str, jnp.ndarray], n: int):
    from ..distributed.ctx import shard_act

    def split(x):
        b = x.shape[0]
        assert b % n == 0, f"batch {b} not divisible by grad_accum {n}"
        y = x.reshape(n, b // n, *x.shape[1:])
        # pin the data sharding to the ROW dim — without this GSPMD may put
        # the batch sharding on the microbatch (scan) dim, which makes every
        # scan iteration process an UNSHARDED 16-row slab (16x the memory
        # and collective payload inside the layer scan).  See §Perf H2.
        return shard_act(y, None, "batch", *([None] * (x.ndim - 1)))
    return jax.tree_util.tree_map(split, batch)


def _quantize_int8(g):
    """Symmetric per-tensor int8 quantization (error feedback upstream)."""
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q.astype(jnp.float32) * scale


def make_train_step(cfg, tcfg: TrainConfig = TrainConfig()):
    def loss_wrapped(params, batch):
        total, (ce, rows) = loss_fn(cfg, params, batch)
        return total, (ce, rows)

    grad_fn = jax.value_and_grad(loss_wrapped, has_aux=True)

    def train_step(params, opt_state: AdamWState, batch):
        if tcfg.grad_accum > 1:
            micro = _split_microbatches(batch, tcfg.grad_accum)

            def acc_body(carry, mb):
                g_acc, l_acc = carry
                (l, (ce, rows)), g = grad_fn(params, mb)
                g_acc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + l), rows

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss_sum), rows_stack = jax.lax.scan(
                acc_body, (zeros, jnp.float32(0.0)), micro)
            grads = jax.tree_util.tree_map(
                lambda g: g / tcfg.grad_accum, grads)
            loss = loss_sum / tcfg.grad_accum
            rows = rows_stack[-1]
        else:
            (loss, (ce, rows)), grads = grad_fn(params, batch)

        if tcfg.compress_grads:
            # int8 EF proxy: quantize the DP all-reduce payload.  Error
            # feedback state lives in the fault-tolerant trainer loop; here
            # the quantization keeps the HLO payload honest for the roofline.
            grads = jax.tree_util.tree_map(_quantize_int8, grads)

        params, opt_state, om = apply_updates(
            tcfg.optimizer, params, opt_state, grads)
        metrics = {"loss": loss, **om}
        return params, opt_state, metrics, rows

    return train_step


def make_serve_step(cfg):
    """One-token decode step: (params, caches, tokens, pos) -> ..."""
    def serve_step(params, caches, tokens, pos):
        logits, new_caches, rows = decode_fn(cfg, params, caches, tokens, pos)
        with jax.named_scope("logits"):
            # mask vocab-padding slots (embed table is padded for sharding)
            pad_mask = jnp.where(
                jnp.arange(logits.shape[-1]) >= cfg.vocab_size, -1e30, 0.0)
            next_tok = jnp.argmax(logits[:, -1, :] + pad_mask,
                                  axis=-1)[:, None]
        return next_tok.astype(tokens.dtype), new_caches, rows

    return serve_step

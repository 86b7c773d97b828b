"""Decoder-only LM assembly: blocks, scan-over-layers, loss, prefill/decode.

Covers the dense, MoE, SSM and VLM-backbone (early-fusion) families.  The
SPRING profile tape is threaded as a first-class output: under the
``shortcut`` policy every scanned block emits one fixed-width record row
(activation stats, attention logit max, MoE expert-buffer fullness) straight
into the stacked [L, width] buffer; under ``inline`` (unrolled layers only)
the faithful growing stream is carried; ``off`` disables collection for
overhead baselines (benchmarks/fig3).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..core import Label, ProfileStream, TapeSpec, rows_to_stream
from ..core.stream import validate_policy
from .attention import attention, decode_attention
from ..distributed.ctx import shard_act
from .common import apply_rotary, rms_norm, write_in_place
from .mla import mla_apply_decode, mla_apply_train, mla_specs
from .mlp import mlp_apply, mlp_specs
from .moe import expert_share_apply, moe_apply, moe_specs
from .params import ParamSpec
from .ssm import (
    SsmCache, ssm_block_apply, ssm_block_decode, ssm_cache_init, ssm_specs,
)

# --------------------------------------------------------------------------- #
# parameter specs
# --------------------------------------------------------------------------- #
def attn_specs(cfg, stacked: int = 0) -> Dict[str, ParamSpec]:
    d, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dtype = cfg.dtype()

    def spec(shape, axes, **kw):
        if stacked:
            return ParamSpec((stacked,) + shape, dtype, ("layers",) + axes, **kw)
        return ParamSpec(shape, dtype, axes, **kw)

    out = {
        "wq": spec((d, H * dh), ("embed", "heads")),
        "wk": spec((d, KV * dh), ("embed", "kv_heads")),
        "wv": spec((d, KV * dh), ("embed", "kv_heads")),
        "wo": spec((H * dh, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        out["bq"] = spec((H * dh,), ("heads",), init="zeros")
        out["bk"] = spec((KV * dh,), ("kv_heads",), init="zeros")
        out["bv"] = spec((KV * dh,), ("kv_heads",), init="zeros")
    if cfg.qk_norm:
        out["q_norm"] = spec((dh,), (None,), init="ones")
        out["k_norm"] = spec((dh,), (None,), init="ones")
    return out


def block_specs(cfg, stacked: int = 0, dense: bool = False) -> Dict[str, Any]:
    """One layer's weights; ``dense`` gives a leading dense layer of an MoE
    model its MLP of width ``dense_d_ff``."""
    dtype = cfg.dtype()

    def nspec(**kw):
        shape, axes = (cfg.d_model,), ("embed_act",)
        if stacked:
            shape, axes = (stacked,) + shape, ("layers",) + axes
        return ParamSpec(shape, dtype, axes, init="ones", **kw)

    if cfg.family == "ssm":
        return {"norm1": nspec(), "ssm": ssm_specs(cfg, stacked)}
    out = {
        "norm1": nspec(),
        "norm2": nspec(),
        "attn": mla_specs(cfg, stacked) if cfg.mla else attn_specs(cfg, stacked),
    }
    if dense:
        out["mlp"] = mlp_specs(cfg.d_model, cfg.dense_d_ff, dtype, stacked)
    elif cfg.family == "moe":
        lo, hi = cfg.held_experts
        out["moe"] = moe_specs(cfg.d_model, cfg.d_ff, cfg.n_experts, dtype,
                               stacked, cfg.n_shared_experts, n_held=hi - lo,
                               router_bias=cfg.router == "sigmoid")
    else:
        out["mlp"] = mlp_specs(cfg.d_model, cfg.d_ff, dtype, stacked,
                               gated=cfg.mlp_gated)
    return out


def lm_specs(cfg) -> Dict[str, Any]:
    dtype = cfg.dtype()
    L = cfg.n_layers if cfg.scan_layers else 0
    specs: Dict[str, Any] = {
        "embed": ParamSpec((cfg.padded_vocab, cfg.d_model), dtype,
                           ("vocab", "embed"), scale=1.0),
        "final_norm": ParamSpec((cfg.d_model,), dtype, ("embed_act",),
                                init="ones"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((cfg.d_model, cfg.padded_vocab), dtype,
                                     ("embed", "vocab"))
    n = cfg.n_layers - cfg.first_k_dense
    if cfg.first_k_dense:
        specs["prefix"] = [block_specs(cfg, dense=True)
                           for _ in range(cfg.first_k_dense)]
    if cfg.scan_layers:
        specs["blocks"] = block_specs(cfg, stacked=n)
    else:
        specs["blocks"] = [block_specs(cfg) for _ in range(n)]
    return specs


# --------------------------------------------------------------------------- #
# profile tape
# --------------------------------------------------------------------------- #
def tape_spec_for(cfg) -> TapeSpec:
    labels = [Label("act_rms", "act_rms", 1), Label("act_absmax", "act_absmax", 1)]
    if cfg.family == "ssm":
        labels.append(Label("state_rms", "state_rms", 1))
    else:
        labels.append(Label("attn_logit_max", "logit_max", 1))
    if cfg.family == "moe" and cfg.router == "sigmoid":
        lo, hi = cfg.held_experts
        labels += [
            Label("expert_tokens", "fifo_fullness", hi - lo),
            Label("unheld_share", "share", 1),
        ]
    elif cfg.family == "moe":
        labels += [
            Label("expert_fullness", "fifo_fullness", cfg.n_experts),
            Label("expert_overflow", "fifo_overflow", cfg.n_experts),
            Label("capacity", "capacity", 1),
        ]
    if cfg.family == "hybrid":
        labels.append(Label("state_rms", "state_rms", 1))
    return TapeSpec(labels=tuple(labels))


# --------------------------------------------------------------------------- #
# block application
# --------------------------------------------------------------------------- #
def _attn_project(cfg, p, x):
    B, T, _ = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, T, H, dh)
    k = k.reshape(B, T, KV, dh)
    v = v.reshape(B, T, KV, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


@jax.named_scope("attn")
def attn_apply_train(cfg, p, x, positions):
    """Full-sequence causal self-attention. Returns (out, logit_max, (k, v))."""
    q, k, v = _attn_project(cfg, p, x)
    q = apply_rotary(q, positions, cfg.rope_theta, cfg.rotary_fraction)
    k = apply_rotary(k, positions, cfg.rope_theta, cfg.rotary_fraction)
    q = shard_act(q, "batch", "seq", "heads", None)
    k = shard_act(k, "batch", "seq", "kv_heads", None)
    v = shard_act(v, "batch", "seq", "kv_heads", None)
    out, lmax = attention(
        q, k, v, impl=cfg.attn_impl, causal=True,
        q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk)
    B, T = x.shape[:2]
    out = shard_act(out.reshape(B, T, -1), "batch", "seq", "heads")
    return out @ p["wo"], lmax, (k, v)


@jax.named_scope("attn")
def attn_apply_decode(cfg, p, x, k_cache, v_cache, layer, pos):
    """One-token attention against layer ``layer`` of the caches stacked
    over layers, [L, B, KV, Smax, dh].  Writes position ``pos`` of that
    layer in place, then reads the layer back.  Returns
    (out, logit_max, (k_cache, v_cache))."""
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q, k, v = _attn_project(cfg, p, x)
    q = apply_rotary(q, positions, cfg.rope_theta, cfg.rotary_fraction)
    k = apply_rotary(k, positions, cfg.rope_theta, cfg.rotary_fraction)
    at = (layer, 0, 0, pos, 0)
    with jax.named_scope("kv_update"):
        k_cache = write_in_place(k_cache, jnp.swapaxes(k, 1, 2)[None], at)
        v_cache = write_in_place(v_cache, jnp.swapaxes(v, 1, 2)[None], at)
    k_l, v_l = (jnp.swapaxes(jax.lax.dynamic_index_in_dim(
        c, layer, keepdims=False), 1, 2) for c in (k_cache, v_cache))
    out, lmax = decode_attention(q, k_l, v_l, pos + 1)
    return out.reshape(B, 1, -1) @ p["wo"], lmax, (k_cache, v_cache)


def _ffn(cfg, p, x):
    """The block's feed-forward slot: (out, aux_loss or None, tape)."""
    if "moe" not in p:
        return mlp_apply(p["mlp"], x, cfg.activation), None, {}
    if cfg.router == "sigmoid":
        return expert_share_apply(
            p["moe"], x, top_k=cfg.top_k, scaling=cfg.routed_scaling,
            held=cfg.held_experts, activation=cfg.activation)
    return moe_apply(p["moe"], x, top_k=cfg.top_k,
                     capacity_factor=cfg.capacity_factor,
                     activation=cfg.activation)


def _attn_train(cfg, p, x, positions):
    """(out, logit_max, what the cache keeps: (k, v) or the MLA latent)."""
    if cfg.mla:
        return mla_apply_train(cfg, p, x, positions)
    return attn_apply_train(cfg, p, x, positions)


def block_apply_train(cfg, p, x, positions):
    """Pre-norm block. Returns (x, tape_values, aux_loss)."""
    aux = jnp.float32(0.0)
    tape: Dict[str, jnp.ndarray] = {}
    if cfg.family == "ssm":
        h, prof = ssm_block_apply(cfg, p["ssm"],
                                  rms_norm(x, p["norm1"], cfg.norm_eps))
        x = x + h
        tape.update(prof)
    else:
        h, lmax, _ = _attn_train(
            cfg, p["attn"], rms_norm(x, p["norm1"], cfg.norm_eps), positions)
        x = x + h
        tape["attn_logit_max"] = lmax[None]
        h, moe_aux, prof = _ffn(cfg, p, rms_norm(x, p["norm2"], cfg.norm_eps))
        if moe_aux is not None:
            aux = aux + cfg.router_aux_weight * moe_aux
        tape.update(prof)
        x = x + h
    x = shard_act(x, "batch", "seq", None)
    xf = x.astype(jnp.float32)
    tape["act_rms"] = jnp.sqrt(jnp.mean(jnp.square(xf)) + 1e-30)[None]
    tape["act_absmax"] = jnp.max(jnp.abs(xf))[None]
    return x, tape, aux


def block_apply_decode(cfg, p, x, cache, layer, pos):
    """cache: the (k, v) tensors or the MLA latent cache stacked over
    layers, of which this block writes position ``pos`` of ``layer`` in
    place; or this layer's SsmCache (``layer`` unused).
    Returns (x, cache, tape)."""
    tape: Dict[str, jnp.ndarray] = {}
    if cfg.family == "ssm":
        h, new_cache, prof = ssm_block_decode(
            cfg, p["ssm"], rms_norm(x, p["norm1"], cfg.norm_eps), cache)
        x = x + h
        tape.update(prof)
    else:
        x_in = rms_norm(x, p["norm1"], cfg.norm_eps)
        if cfg.mla:
            h, lmax, new_cache = mla_apply_decode(cfg, p["attn"], x_in,
                                                  cache, layer, pos)
        else:
            h, lmax, new_cache = attn_apply_decode(cfg, p["attn"], x_in,
                                                   *cache, layer, pos)
        x = x + h
        tape["attn_logit_max"] = lmax[None]
        h, _, prof = _ffn(cfg, p, rms_norm(x, p["norm2"], cfg.norm_eps))
        tape.update(prof)
        x = x + h
    xf = x.astype(jnp.float32)
    tape["act_rms"] = jnp.sqrt(jnp.mean(jnp.square(xf)) + 1e-30)[None]
    tape["act_absmax"] = jnp.max(jnp.abs(xf))[None]
    return x, new_cache, tape


# --------------------------------------------------------------------------- #
# remat policies
# --------------------------------------------------------------------------- #
def _remat(fn, cfg):
    if not cfg.remat:
        return fn
    policies = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "full": jax.checkpoint_policies.everything_saveable,
    }
    return jax.checkpoint(fn, policy=policies[cfg.remat_policy])


# --------------------------------------------------------------------------- #
# forward / loss
# --------------------------------------------------------------------------- #
@jax.named_scope("embed")
def _embed(cfg, params, tokens):
    return params["embed"][tokens].astype(jnp.dtype(cfg.activation_dtype))


def lm_hidden(cfg, params, tokens, positions):
    """Token ids -> final hidden states.  Returns (h, rows, aux)."""
    spec = tape_spec_for(cfg)
    pdtype = jnp.dtype(cfg.profile_dtype)
    policy = validate_policy(cfg.profile_policy)
    x = _embed(cfg, params, tokens)
    x = shard_act(x, "batch", "seq", None)
    x, pre_rows, aux_pre = _prefix_train(cfg, params, x, positions, spec,
                                         pdtype, policy)

    if cfg.scan_layers:
        def body(carry, per_layer):
            xc, aux = carry
            p_l = per_layer
            xc, tape, aux_l = block_apply_train(cfg, p_l, xc, positions)
            row = (spec.emit(tape, pdtype) if policy == "shortcut"
                   else jnp.zeros((0,), pdtype))
            return (xc, aux + aux_l), row

        body = _remat(body, cfg)
        (x, aux), rows = jax.lax.scan(body, (x, aux_pre), params["blocks"])
    else:
        aux = aux_pre
        row_list = []
        for p_l in params["blocks"]:
            x, tape, aux_l = block_apply_train(cfg, p_l, x, positions)
            aux = aux + aux_l
            if policy != "off":
                row_list.append(spec.emit(tape, pdtype))
        rows = (jnp.stack(row_list) if (row_list and policy != "off")
                else jnp.zeros((len(params["blocks"]), 0), pdtype))

    if pre_rows is not None:
        rows = jnp.concatenate([pre_rows, rows])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, rows, aux


def _rows(cfg, spec, tapes, pdtype, policy):
    """Profile rows [len(tapes), width] of the leading dense layers, as the
    layers after them emit theirs (scanned: shortcut only)."""
    if policy == "shortcut" or (policy != "off" and not cfg.scan_layers):
        return jnp.stack([spec.emit(t, pdtype) for t in tapes])
    return jnp.zeros((len(tapes), 0), pdtype)


def _prefix_train(cfg, params, x, positions, spec, pdtype, policy):
    """The leading dense layers, before the scanned stack.
    Returns (x, their profile rows or None, aux)."""
    aux = jnp.float32(0.0)
    if "prefix" not in params:
        return x, None, aux
    tapes = []
    for p_l in params["prefix"]:
        x, tape, aux_l = block_apply_train(cfg, p_l, x, positions)
        aux = aux + aux_l
        tapes.append(tape)
    return x, _rows(cfg, spec, tapes, pdtype, policy), aux


@jax.named_scope("logits")
def lm_logits(cfg, params, h):
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    return h @ head


def chunked_ce_loss(cfg, params, h, labels):
    """Cross-entropy with the vocab projection chunked over sequence."""
    B, S, d = h.shape
    chunk = min(cfg.loss_chunk, S)
    if S % chunk:
        chunk = S
    n = S // chunk
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    # FSDP gather-at-use: unshard the head's embed (data) dim here so XLA
    # gathers the small weight once rather than the huge logits/activations.
    head = shard_act(head, None, "vocab")
    pad_mask = (jnp.arange(cfg.padded_vocab) >= cfg.vocab_size) * -1e30

    @jax.checkpoint
    def body(carry, idx):
        total, cnt = carry
        hc = jax.lax.dynamic_slice_in_dim(h, idx * chunk, chunk, axis=1)
        lc = jax.lax.dynamic_slice_in_dim(labels, idx * chunk, chunk, axis=1)
        logits = shard_act((hc @ head).astype(jnp.float32) + pad_mask,
                           "batch", "seq", "vocab")
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        total = total + jnp.sum((logz - gold) * mask)
        cnt = cnt + jnp.sum(mask)
        return (total, cnt), None

    (total, cnt), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), jnp.arange(n))
    return total / jnp.maximum(cnt, 1.0)


def lm_loss(cfg, params, tokens, labels):
    """Next-token loss + profile stream rows.  tokens/labels: [B, S]."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    h, rows, aux = lm_hidden(cfg, params, tokens, positions)
    loss = chunked_ce_loss(cfg, params, h, labels)
    return loss + aux, (loss, rows)


def assemble_stream(cfg, rows) -> Optional[ProfileStream]:
    if cfg.profile_policy == "off" or rows.shape[-1] == 0:
        return None
    return rows_to_stream(tape_spec_for(cfg), rows, layer_prefix="block")


# --------------------------------------------------------------------------- #
# serving: prefill + decode
# --------------------------------------------------------------------------- #
class KvCaches(NamedTuple):
    """Keys and values per layer and position, [L, B, KV, Smax, dh]: one
    kv head's positions are rows of ``dh``, as decode attention reads
    them.  The decode step writes one position a layer in place
    (``lm_decode_step``)."""
    k: jnp.ndarray
    v: jnp.ndarray


class LatentCaches(NamedTuple):
    """MLA's cache: per layer and position the normalised latent and the
    rotated rope key, [layers, B, Smax, latent_row(cfg)], for the leading
    dense layers and for the scanned stack.  The decode step writes one
    position a layer of each in place (``lm_decode_step``)."""
    prefix: jnp.ndarray
    blocks: jnp.ndarray


def latent_row(cfg) -> int:
    """A latent cache row: ``kv_lora_rank + qk_rope_dim`` padded to a
    multiple of 128.  The TPU keeps a minor dimension of that width minor,
    so the cache stays row-major on the device, as ``write_in_place``
    needs.  The padding is written once, as zeros; decode attention
    (``kernels.mla_decode``) reads it with the row and weighs it by the
    query's zero padding."""
    return -(-cfg.latent_dim // 128) * 128


def kv_cache_init(cfg, batch: int, max_len: int):
    dt = jnp.dtype(cfg.activation_dtype)
    if cfg.mla:
        k = cfg.first_k_dense
        tail = (batch, max_len, latent_row(cfg))
        return LatentCaches(jnp.zeros((k,) + tail, dt),
                            jnp.zeros((cfg.n_layers - k,) + tail, dt))
    dh = cfg.head_dim
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, dh)
    return KvCaches(jnp.zeros(shape, dt), jnp.zeros(shape, dt))


def ssm_caches_init(cfg, batch: int):
    dt = jnp.dtype(cfg.activation_dtype)
    one = ssm_cache_init(cfg, batch, dt)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape), one)


def lm_decode_step(cfg, params, caches, tokens, pos):
    """One decode step.  tokens: [B, 1]; caches stacked over layers.

    The position-indexed caches (``KvCaches``, ``LatentCaches``) ride in
    the layer scan's carry, and each layer writes its one position into
    the stacked buffer in place before its attention reads the layer.
    Passed through the scan's ``xs`` and ``ys`` instead, they would be a
    second stacked buffer that every layer rewrites a whole slice of and
    that is copied back into the donated cache after the loop.  An
    ``SsmCache`` changes whole every step, so it is scanned over as ``xs``
    and ``ys``.

    Returns (logits [B, 1, V], caches, rows).
    """
    spec = tape_spec_for(cfg)
    pdtype = jnp.dtype(cfg.profile_dtype)
    policy = validate_policy(cfg.profile_policy)
    x = _embed(cfg, params, tokens)

    def emit(tape):
        return (spec.emit(tape, pdtype) if policy == "shortcut"
                else jnp.zeros((0,), pdtype))

    if cfg.family == "ssm":
        def ssm_body(xc, per_layer):
            p_l, cache_l = per_layer
            xc, cache_l, tape = block_apply_decode(cfg, p_l, xc, cache_l,
                                                   None, pos)
            return xc, (cache_l, emit(tape))

        x, (new_caches, rows) = jax.lax.scan(ssm_body, x,
                                             (params["blocks"], caches))
    else:
        def body(carry, per_layer):
            xc, stack = carry
            p_l, layer = per_layer
            xc, stack, tape = block_apply_decode(cfg, p_l, xc, stack,
                                                 layer, pos)
            return (xc, stack), emit(tape)

        if cfg.mla:
            # the leading dense layers, then the stack
            prefix, tapes = caches.prefix, []
            for layer, p_l in enumerate(params["prefix"]):
                x, prefix, tape = block_apply_decode(cfg, p_l, x, prefix,
                                                     layer, pos)
                tapes.append(tape)
            stack = caches.blocks
        else:
            stack = (caches.k, caches.v)
        layers = jnp.arange(cfg.n_layers - cfg.first_k_dense)
        (x, stack), rows = jax.lax.scan(body, (x, stack),
                                        (params["blocks"], layers))
        if cfg.mla:
            new_caches = LatentCaches(prefix, stack)
            rows = jnp.concatenate([_rows(cfg, spec, tapes, pdtype, policy),
                                    rows])
        else:
            new_caches = KvCaches(*stack)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(cfg, params, x)
    return logits, new_caches, rows


def lm_prefill(cfg, params, tokens):
    """Prefill: returns (last-position logits, caches filled to S)."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = _embed(cfg, params, tokens)

    def body(carry, p_l):
        xc = carry
        if cfg.family == "ssm":
            h, prof = ssm_block_apply(
                cfg, p_l["ssm"], rms_norm(xc, p_l["norm1"], cfg.norm_eps))
            xc = xc + h
            # SSD final state is recomputed per layer for the cache below
            return xc, None
        h, lmax, kept = _attn_train(
            cfg, p_l["attn"], rms_norm(xc, p_l["norm1"], cfg.norm_eps),
            positions)
        xc = xc + h
        h, _, _ = _ffn(cfg, p_l, rms_norm(xc, p_l["norm2"], cfg.norm_eps))
        xc = xc + h
        return xc, kept

    pre = []
    for p_l in params.get("prefix", ()):
        x, latent = body(x, p_l)
        pre.append(latent)
    x, kept = jax.lax.scan(body, x, params["blocks"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits_last = lm_logits(cfg, params, x[:, -1:, :])
    if cfg.family == "ssm":
        caches = None
    elif cfg.mla:
        pad = [(0, 0)] * 3 + [(0, latent_row(cfg) - cfg.latent_dim)]
        caches = LatentCaches(jnp.pad(jnp.stack(pre), pad), jnp.pad(kept, pad))
    else:
        caches = KvCaches(*(jnp.swapaxes(c, 2, 3) for c in kept))
    return logits_last, caches

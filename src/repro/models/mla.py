"""Multi-head latent attention (MLA, DeepSeek-V2/V3) with a latent cache.

Keys and values are compressed to one ``kv_lora_rank``-wide latent per
position, plus one ``qk_rope_dim``-wide rotary key shared by all heads:

  * ``wkv_a`` (HF ``kv_a_proj_with_mqa``) gives ``[c_kv | k_pe]``;
  * ``c_kv`` is RMS-normalised (``kv_norm``, HF ``kv_a_layernorm``), then
    ``wkv_b`` (HF ``kv_b_proj``) gives each head's ``k_nope`` and ``v``;
  * queries (HF ``q_proj``; no query compression) are ``[q_nope | q_pe]``;
  * rotary applies to ``q_pe`` and ``k_pe`` only; scores scale by
    ``1/sqrt(qk_nope_dim + qk_rope_dim)``.

Two paths compute the same attention:

  * plain (train, prefill): decompress per-head keys and values and run the
    ordinary attention with q/k wider than v;
  * absorbed (decode): the cache holds only ``[c_kv | k_pe]`` per position
    and layer; ``W_UK`` is folded into the query and ``W_UV`` into the
    output, so the cache is never decompressed.

Rotary pairs are interleaved (``common.apply_rotary``); the published code
de-interleaves before ``rotate_half``.  The two differ by a fixed
permutation of the rope dims applied to queries and keys alike, which
leaves every score unchanged.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from .attention import NEG_INF, attention
from .common import apply_rotary, rms_norm, write_in_place
from .params import ParamSpec


def mla_specs(cfg, stacked: int = 0) -> Dict[str, ParamSpec]:
    d, H = cfg.d_model, cfg.n_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    dtype = cfg.dtype()

    def spec(shape, axes, **kw):
        if stacked:
            return ParamSpec((stacked,) + shape, dtype, ("layers",) + axes, **kw)
        return ParamSpec(shape, dtype, axes, **kw)

    return {
        "wq": spec((d, H * (dn + dr)), ("embed", "heads")),
        "wkv_a": spec((d, r + dr), ("embed", None)),
        "kv_norm": spec((r,), (None,), init="ones"),
        "wkv_b": spec((r, H * (dn + dv)), (None, "heads")),
        "wo": spec((H * dv, d), ("heads", "embed")),
    }


def _project(cfg, p, x, positions):
    """Queries and the position's latent: (q_nope, q_pe, latent)."""
    B, T, _ = x.shape
    H, r, dn = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim
    q = (x @ p["wq"]).reshape(B, T, H, -1)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    kv_a = x @ p["wkv_a"]                                   # [B, T, r + dr]
    c_kv = rms_norm(kv_a[..., :r], p["kv_norm"], cfg.norm_eps)
    q_pe = apply_rotary(q_pe, positions, cfg.rope_theta)
    k_pe = apply_rotary(kv_a[..., None, r:], positions, cfg.rope_theta)
    return q_nope, q_pe, jnp.concatenate([c_kv, k_pe[:, :, 0]], axis=-1)


def _scale(cfg) -> float:
    return 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)


@jax.named_scope("attn")
def mla_apply_train(cfg, p, x, positions):
    """Plain path over a whole sequence.  Returns (out, logit_max, latent
    [B, T, r + dr])."""
    B, T, _ = x.shape
    H, r, dn = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim
    q_nope, q_pe, latent = _project(cfg, p, x, positions)
    kv = (latent[..., :r] @ p["wkv_b"]).reshape(B, T, H, -1)
    k_pe = jnp.broadcast_to(latent[:, :, None, r:],
                            (B, T, H, cfg.qk_rope_dim))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :dn], k_pe], axis=-1)
    out, lmax = attention(
        q, k, kv[..., dn:], impl=cfg.attn_impl, causal=True,
        q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk)
    return out.reshape(B, T, -1) @ p["wo"], lmax, latent


@jax.named_scope("attn")
def mla_apply_decode(cfg, p, x, caches, layer, pos):
    """Absorbed path: one token against layer ``layer`` of the latent caches
    stacked over layers, [L, B, Smax, row] (rows of ``r + dr`` padded to
    the lane width).  Writes position ``pos`` of that layer in place, then
    reads the layer back.  Returns (out, logit_max, caches)."""
    B = x.shape[0]
    H, r, dn = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim
    positions = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_pe, latent = _project(cfg, p, x, positions)
    with jax.named_scope("latent_update"):
        caches = write_in_place(caches, latent[None], (layer, 0, pos, 0))
    cache = jax.lax.dynamic_index_in_dim(caches, layer, keepdims=False)
    cache = cache[..., :latent.shape[-1]]                    # [B, Smax, r+dr]
    wkv_b = p["wkv_b"].reshape(r, H, -1)
    q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, wkv_b[..., :dn])
    q_cat = jnp.concatenate([q_lat, q_pe], axis=-1)         # [B, 1, H, r+dr]
    logits = jnp.einsum("bthc,bsc->bhts", q_cat, cache,
                        preferred_element_type=jnp.float32) * _scale(cfg)
    valid = jnp.arange(cache.shape[1]) < pos + 1
    logits = jnp.where(valid, logits, NEG_INF)
    lmax = jnp.max(logits)
    w = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhts,bsr->bthr", w, cache[..., :r])
    out = jnp.einsum("bthr,rhv->bthv", o_lat, wkv_b[..., dn:])
    return out.reshape(B, 1, -1) @ p["wo"], lmax, caches

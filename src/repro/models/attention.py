"""Grouped-query attention with three execution paths.

  * ``naive``      — full [T, S] scores; smoke tests and tiny shapes.
  * ``flash_tri``  — double-chunked online-softmax with *causal block
                     skipping*: a Python loop over Q chunks, each attending
                     only to its KV prefix — triangular FLOPs, bounded
                     memory.  The XLA-level adaptation of FlashAttention's
                     TPU form (the Pallas kernel in repro.kernels is the
                     in-kernel version; this one exists so the dry-run HLO
                     carries real cost structure on any backend).
  * ``flash_scan`` — ``lax.scan`` over KV chunks with masking (compact HLO
                     for very long sequences; full S·T FLOPs).

All paths return ``(output, logit_max)`` — the max attention logit is the
in-band profiling tap (overflow sentinel), SPRING-style.

GQA is computed in grouped form [B, T, KV, G, Dh] without materializing
repeated KV heads.  Values may be narrower than queries and keys (latent
attention's plain path: q/k 192 wide, v 128): outputs and accumulators take
v's width, the softmax scale q's.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _group(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """[B, T, H, Dh] -> [B, T, KV, G, Dh]."""
    b, t, h, dh = q.shape
    return q.reshape(b, t, n_kv, h // n_kv, dh)


def _scores(qg: jnp.ndarray, k: jnp.ndarray, scale: float) -> jnp.ndarray:
    """einsum to [B, KV, G, Tq, Tk] in fp32."""
    return jnp.einsum("btkgd,bskd->bkgts", qg, k,
                      preferred_element_type=jnp.float32) * scale


def naive_attention(
    q, k, v, *, causal: bool, q_offset=0, bias: Optional[jnp.ndarray] = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, t, h, dh = q.shape
    s = k.shape[1]
    kv = k.shape[2]
    qg = _group(q, kv)
    logits = _scores(qg, k, 1.0 / math.sqrt(dh))
    if causal:
        q_pos = q_offset + jnp.arange(t)[:, None]
        kv_pos = jnp.arange(s)[None, :]
        logits = logits + jnp.where(kv_pos <= q_pos, 0.0, NEG_INF)
    if bias is not None:
        logits = logits + bias
    lmax = jnp.max(logits)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", w, v)
    return out.reshape(b, t, h, v.shape[-1]), lmax


def _online_update(m, l, acc, logits, v_chunk):
    """One online-softmax accumulation step (fp32 state)."""
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1))           # [B,KV,G,T]
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new[..., None])                     # [B,KV,G,T,S]
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bkgts,bskd->bkgtd", p.astype(v_chunk.dtype), v_chunk,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def flash_tri_attention(
    q, k, v, *, q_chunk: int, kv_chunk: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Causal self-attention with triangular block skipping (training path).

    Requires T == S (self-attention from position 0).
    """
    b, t, h, dh = q.shape
    s, kv = k.shape[1], k.shape[2]
    assert t == s, "flash_tri is a self-attention training path"
    qc = min(q_chunk, t)
    kc = min(kv_chunk, s)
    n_q = math.ceil(t / qc)
    scale = 1.0 / math.sqrt(dh)
    outs, lmaxes = [], []
    for i in range(n_q):
        q0 = i * qc
        q_len = min(qc, t - q0)
        qg = _group(q[:, q0:q0 + q_len], kv)
        kv_hi = q0 + q_len                       # causal prefix only
        n_k = math.ceil(kv_hi / kc)
        m = jnp.full((b, kv, h // kv, q_len), NEG_INF, jnp.float32)
        l = jnp.zeros((b, kv, h // kv, q_len), jnp.float32)
        acc = jnp.zeros((b, kv, h // kv, q_len, v.shape[-1]), jnp.float32)
        for j in range(n_k):
            k0 = j * kc
            k_len = min(kc, kv_hi - k0)
            logits = _scores(qg, k[:, k0:k0 + k_len], scale)
            # only the diagonal block needs a mask
            if k0 + k_len > q0:
                q_pos = q0 + jnp.arange(q_len)[:, None]
                kv_pos = k0 + jnp.arange(k_len)[None, :]
                logits = logits + jnp.where(kv_pos <= q_pos, 0.0, NEG_INF)
            m, l, acc = _online_update(m, l, acc, logits, v[:, k0:k0 + k_len])
        out_i = (acc / l[..., None]).astype(q.dtype)   # [b, kv, g, q_len, dh]
        outs.append(out_i.transpose(0, 3, 1, 2, 4).reshape(b, q_len, h, -1))
        lmaxes.append(jnp.max(m))
    return jnp.concatenate(outs, axis=1), jnp.max(jnp.stack(lmaxes))


def flash_scan_attention(
    q, k, v, *, causal: bool, q_offset=0, kv_chunk: int = 2048
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Online-softmax attention scanning KV chunks (compact HLO, long S)."""
    b, t, h, dh = q.shape
    s, n_kv, dv = k.shape[1], k.shape[2], v.shape[-1]
    kc = min(kv_chunk, s)
    if s % kc:  # pad KV to a chunk multiple; padded positions are masked out
        pad = kc - s % kc
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    s_pad = k.shape[1]
    n_chunks = s_pad // kc
    qg = _group(q, n_kv)
    scale = 1.0 / math.sqrt(dh)
    kr = k.reshape(b, n_chunks, kc, n_kv, dh).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(b, n_chunks, kc, n_kv, dv).transpose(1, 0, 2, 3, 4)
    del k, v

    def body(carry, chunk):
        m, l, acc, j = carry
        kc_, vc_ = chunk
        logits = _scores(qg, kc_, scale)
        kv_pos = j * kc + jnp.arange(kc)[None, :]
        if causal:
            q_pos = q_offset + jnp.arange(t)[:, None]
            logits = logits + jnp.where(kv_pos <= q_pos, 0.0, NEG_INF)
        if s_pad != s:  # mask KV padding
            logits = logits + jnp.where(kv_pos < s, 0.0, NEG_INF)
        m, l, acc = _online_update(m, l, acc, logits, vc_)
        return (m, l, acc, j + 1), None

    g = h // n_kv
    init = (
        jnp.full((b, n_kv, g, t), NEG_INF, jnp.float32),
        jnp.zeros((b, n_kv, g, t), jnp.float32),
        jnp.zeros((b, n_kv, g, t, dv), jnp.float32),
        jnp.int32(0),
    )
    (m, l, acc, _), _ = jax.lax.scan(body, init, (kr, vr))
    out = (acc / l[..., None]).astype(q.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, dv), jnp.max(m)


def decode_attention(
    q,                      # [B, 1, H, Dh]
    k_cache, v_cache,       # [B, S, KV, Dh]
    cache_len,              # [] int — valid positions
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token attention over a (possibly padded) KV cache."""
    b, t, h, dh = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    qg = _group(q, kv)
    logits = _scores(qg, k_cache, 1.0 / math.sqrt(dh))
    valid = (jnp.arange(s) < cache_len)[None, None, None, None, :]
    logits = jnp.where(valid, logits, NEG_INF)
    lmax = jnp.max(logits)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", w, v_cache)
    return out.reshape(b, t, h, dh), lmax


def attention(
    q, k, v, *, impl: str, causal: bool = True, q_offset=0,
    q_chunk: int = 1024, kv_chunk: int = 1024,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if impl == "naive" or q.shape[1] <= max(64, q_chunk // 8):
        return naive_attention(q, k, v, causal=causal, q_offset=q_offset)
    if impl == "flash_tri" and causal and q.shape[1] == k.shape[1]:
        return flash_tri_attention(q, k, v, q_chunk=q_chunk, kv_chunk=kv_chunk)
    if impl in ("flash_scan", "flash_tri"):
        return flash_scan_attention(q, k, v, causal=causal, q_offset=q_offset,
                                    kv_chunk=kv_chunk)
    raise ValueError(f"unknown attention impl {impl!r}")

"""Plain float32 reference of the chatglm3-6b decoder, and its fp8 control.

Written from the published architecture (``bench/configs/chatglm3-6b.json``)
in straightforward ``jax.numpy``: no cache, no kernels, every matmul at
``Precision.HIGHEST``.  It imports nothing of the program.  The weights are
drawn again from the run's seed by the same recipe the program uses (one key
per leaf from ``jax.random.split``, leaves in the order of the parameter
tree, every matrix ``N(0, 1/fan_in)`` in float32 then rounded to the served
dtype), so the reference sees the served model's values without taking any
array from the program.

The model runs one layer at a time, so only one layer's weights are ever
held in float32.

``quant="fp8"`` is the control: every matmul operand (weights per output
column, activations per row) rounded to float8_e4m3 with a float32 scale,
accumulation in float32 -- the step below the bfloat16 the configuration
serves in.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _dims(cfg: dict):
    return (cfg["num_layers"], cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["multi_query_group_num"], cfg["kv_channels"],
            cfg["ffn_hidden_size"], cfg["padded_vocab_size"])


def leaf_table(cfg: dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """``(name, shape, init)`` of every weight, in the order the program's
    parameter tree flattens (dict keys sorted, layers stacked on axis 0)."""
    L, d, H, KV, dh, ff, V = _dims(cfg)
    return [
        ("attn.wk", (L, d, KV * dh), "normal"),
        ("attn.wo", (L, H * dh, d), "normal"),
        ("attn.wq", (L, d, H * dh), "normal"),
        ("attn.wv", (L, d, KV * dh), "normal"),
        ("mlp.wg", (L, d, ff), "normal"),
        ("mlp.wi", (L, d, ff), "normal"),
        ("mlp.wo", (L, ff, d), "normal"),
        ("norm1", (L, d), "ones"),
        ("norm2", (L, d), "ones"),
        ("embed", (V, d), "embed"),
        ("final_norm", (d,), "ones"),
        ("lm_head", (d, V), "normal"),
    ]


def served_dtype(cfg: dict):
    return jnp.dtype(cfg["torch_dtype"])


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_weights(cfg: dict, seed: int) -> Dict[str, jnp.ndarray]:
    """The served weights for ``seed``, on the default device."""
    table = leaf_table(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(table))
    dtype = served_dtype(cfg)
    out = {}
    for (name, shape, init), key in zip(table, keys):
        if init == "ones":
            out[name] = jnp.ones(shape, dtype)
            continue
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        std = 1.0 if init == "embed" else 1.0 / math.sqrt(max(1, fan_in))
        out[name] = _draw(key, shape, std, dtype)
    return out


def _q8(a, axis):
    """Round to float8_e4m3 with one float32 scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant: Optional[str]):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rotary(x, positions, rot: int, theta: float = 10000.0):
    """Rotate the first ``rot`` dims of each head, pairs interleaved."""
    xr, xp = x[..., :rot], x[..., rot:]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[:, None] * inv        # [S, rot/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    y = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([y.reshape(xr.shape), xp], axis=-1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(dims, quant, x, wq, wk, wv, wo, wg, wi, wmo, n1, n2, eps):
    _, d, H, KV, dh, _, _ = dims
    R, S, _ = x.shape
    pos = jnp.arange(S)
    h = _rms(x, n1, eps)
    q = _mm(h, wq, quant).reshape(R, S, H, dh)
    k = _mm(h, wk, quant).reshape(R, S, KV, dh)
    v = _mm(h, wv, quant).reshape(R, S, KV, dh)
    q, k = _rotary(q, pos, dh // 2), _rotary(k, pos, dh // 2)
    q = q.reshape(R, S, KV, H // KV, dh)
    s = jnp.einsum("rsvgd,rtvd->rvgst", q, k, precision=HI) / math.sqrt(dh)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("rvgst,rtvd->rsvgd", p, v, precision=HI)
    x = x + _mm(o.reshape(R, S, H * dh), wo, quant)
    h = _rms(x, n2, eps)
    x = x + _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wi, quant), wmo,
                quant)
    return x


@functools.partial(jax.jit, static_argnums=(0,))
def _head(quant, x, final_norm, lm_head, eps):
    return _mm(_rms(x, final_norm, eps), lm_head, quant)


def logits(cfg: dict, w: Dict[str, jnp.ndarray], tokens: np.ndarray,
           quant: Optional[str] = None) -> jnp.ndarray:
    """float32 logits ``[R, S, V]`` of token rows ``tokens`` ``[R, S]``."""
    dims = _dims(cfg)
    eps = jnp.float32(cfg["layernorm_epsilon"])
    x = w["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(dims[0]):
        x = _layer(dims, quant, x, w["attn.wq"][i], w["attn.wk"][i],
                   w["attn.wv"][i], w["attn.wo"][i], w["mlp.wg"][i],
                   w["mlp.wi"][i], w["mlp.wo"][i], w["norm1"][i],
                   w["norm2"][i], eps)
    return _head(quant, x, w["final_norm"], w["lm_head"], eps)


def served_gap(ref: np.ndarray, tokens: np.ndarray, prompt_len: int
               ) -> float:
    """Widest gap by which a served token's reference logit lies below the
    reference's best at the position that produced it.

    ``ref`` ``[R, S, V]``; ``tokens`` ``[R, S]``, prompt then served tokens.
    The token at position ``p >= prompt_len`` came from position ``p - 1``.
    """
    prev = ref[:, prompt_len - 1:-1]                       # [R, G, V]
    served = tokens[:, prompt_len:]                         # [R, G]
    got = np.take_along_axis(prev, served[..., None], -1)[..., 0]
    return float((prev.max(-1) - got).max())


def control_gap(ref: np.ndarray, ctrl: np.ndarray, prompt_len: int) -> float:
    """The same gap for the tokens the control would put first, at every
    position that produced a served token."""
    prev = ref[:, prompt_len - 1:-1]
    pick = ctrl[:, prompt_len - 1:-1].argmax(-1)
    got = np.take_along_axis(prev, pick[..., None], -1)[..., 0]
    return float((prev.max(-1) - got).max())

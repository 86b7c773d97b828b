"""Plain float32 reference of Moonlight-16B-A3B (DeepSeek-V3 architecture)
holding one share of the routed experts, and its fp8 control.

Written from the published architecture (``bench/configs/
moonlight-16b-a3b.json``, Hugging Face key names) in straightforward
``jax.numpy``: no cache, no kernels, no absorbed attention, every matmul at
``Precision.HIGHEST``.  It imports nothing of the program.

* Latent attention, as the published modeling code computes it: per-head
  keys and values are decompressed from the normalised latent through
  ``kv_b_proj``; rotary on the 64-wide rope parts, the rope key shared by
  all heads; scale ``1/sqrt(qk_nope_head_dim + qk_rope_head_dim)``.
* Layers below ``first_k_dense_replace`` have a dense SwiGLU MLP of width
  ``intermediate_size``; the rest are MoE layers: sigmoid scores over the
  router's ``expert_parallel.router_width`` experts, selection by scores
  plus the correction bias, weights from the scores alone, renormalised
  and times ``routed_scaling_factor``.  Only the held experts
  ``expert_parallel.held_experts`` add their part, each over every token
  at its routing weight; the shared experts add theirs whole.

The weights are drawn again from the run's seed by the same recipe the
program uses (one key per leaf from ``jax.random.split``, leaves in the
order of the parameter tree, every matrix ``N(0, 1/fan_in)`` in float32
then rounded to the served dtype, the correction bias ``N(0, 0.05^2)`` in
float32), so the reference sees the served model's values without taking
any array from the program.  The model runs one layer at a time, so only
one layer's weights are ever held in float32.

``quant="fp8"`` is the control: every matmul operand rounded to
float8_e4m3 with a float32 scale (weights per output column, activations
per row), accumulation in float32, the step below the bfloat16 served.
``quant="bf16"`` rounds every operand to bfloat16: the served precision,
for counting the expert selections that rounding flips.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.chatglm import FP8_MAX

HI = jax.lax.Precision.HIGHEST
BIAS_STD = 0.05


def _dims(cfg: dict) -> tuple:
    ep = cfg["expert_parallel"]
    lo, hi = ep["held_experts"]
    return (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            ep["router_width"], hi - lo, cfg["n_shared_experts"],
            cfg["num_experts_per_tok"], cfg["vocab_size"])


def _attn_table(prefix: str, lead: tuple, cfg: dict) -> list:
    _, _, d, H, r, dn, dr, dv = _dims(cfg)[:8]
    return [
        (prefix + "attn.kv_norm", lead + (r,), "ones"),
        (prefix + "attn.wkv_a", lead + (d, r + dr), "normal"),
        (prefix + "attn.wkv_b", lead + (r, H * (dn + dv)), "normal"),
        (prefix + "attn.wo", lead + (H * dv, d), "normal"),
        (prefix + "attn.wq", lead + (d, H * (dn + dr)), "normal"),
    ]


def leaf_table(cfg: dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """``(name, shape, init)`` of every weight, in the order the program's
    parameter tree flattens (dict keys sorted, MoE layers stacked on axis
    0, the leading dense layers a list after ``lm_head``)."""
    L, K, d, _, _, _, _, _, ff, f, E, Eh, ns, _, V = _dims(cfg)
    n = (L - K,)
    out = _attn_table("blocks.", n, cfg) + [
        ("blocks.moe.router", n + (d, E), "normal"),
        ("blocks.moe.router_bias", n + (E,), "bias"),
        ("blocks.moe.shared_wg", n + (d, ns * f), "normal"),
        ("blocks.moe.shared_wi", n + (d, ns * f), "normal"),
        ("blocks.moe.shared_wo", n + (ns * f, d), "normal"),
        ("blocks.moe.w1", n + (Eh, d, f), "normal"),
        ("blocks.moe.w2", n + (Eh, f, d), "normal"),
        ("blocks.moe.wg", n + (Eh, d, f), "normal"),
        ("blocks.norm1", n + (d,), "ones"),
        ("blocks.norm2", n + (d,), "ones"),
        ("embed", (V, d), "embed"),
        ("final_norm", (d,), "ones"),
        ("lm_head", (d, V), "normal"),
    ]
    for i in range(K):
        p = f"prefix.{i}."
        out += _attn_table(p, (), cfg) + [
            (p + "mlp.wg", (d, ff), "normal"),
            (p + "mlp.wi", (d, ff), "normal"),
            (p + "mlp.wo", (ff, d), "normal"),
            (p + "norm1", (d,), "ones"),
            (p + "norm2", (d,), "ones"),
        ]
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_weights(cfg: dict, seed: int) -> Dict[str, jnp.ndarray]:
    """The served weights for ``seed``, on the default device."""
    table = leaf_table(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(table))
    dtype = jnp.dtype(cfg["torch_dtype"])
    out = {}
    for (name, shape, init), key in zip(table, keys):
        if init == "ones":
            out[name] = jnp.ones(shape, dtype)
        elif init == "bias":
            out[name] = _draw(key, shape, BIAS_STD, jnp.float32)
        else:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            std = 1.0 if init == "embed" else 1.0 / math.sqrt(fan_in)
            out[name] = _draw(key, shape, std, dtype)
    return out


def _round(a, axis, quant):
    if quant == "bf16":
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant: Optional[str]):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant is not None:
        x, w = _round(x, -1, quant), _round(w, -2, quant)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rotary(x, positions, theta):
    """Rotate all dims of each head, pairs interleaved (the published code's
    layout up to one fixed permutation of q and k alike)."""
    rot = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(h, wg, wi, wo, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wi, quant), wo, quant)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _attention(dims, quant, x, a, n1, eps, theta):
    """x plus the latent attention of one layer; ``a`` its attn weights."""
    _, _, _, H, r, dn, dr, dv = dims[:8]
    R, S, _ = x.shape
    pos = jnp.arange(S)
    h = _rms(x, n1, eps)
    q = _mm(h, a["wq"], quant).reshape(R, S, H, dn + dr)
    kv_a = _mm(h, a["wkv_a"], quant)
    c_kv = _rms(kv_a[..., :r], a["kv_norm"], eps)
    kv = _mm(c_kv, a["wkv_b"], quant).reshape(R, S, H, dn + dv)
    k_pe = _rotary(kv_a[:, :, None, r:], pos, theta)
    q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], pos, theta)], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (R, S, H, dr))], -1)
    s = jnp.einsum("rshd,rthd->rhst", q, k, precision=HI) / math.sqrt(dn + dr)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = jnp.einsum("rhst,rthd->rshd", jax.nn.softmax(s, axis=-1),
                   kv[..., dn:], precision=HI)
    return x + _mm(o.reshape(R, S, H * dv), a["wo"], quant)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _dense_mlp(dims, quant, x, m, n2, eps):
    h = _rms(x, n2, eps)
    return x + _swiglu(h, m["wg"], m["wi"], m["wo"], quant)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _moe(dims, quant, lo, x, m, n2, eps, scaling):
    """x plus the held experts' part and the shared experts; and the
    selected experts [R, S, k]."""
    E, Eh, top_k = dims[10], dims[11], dims[13]
    h = _rms(x, n2, eps)
    scores = jax.nn.sigmoid(_mm(h, m["router"], quant))
    _, idx = jax.lax.top_k(scores + m["router_bias"], top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scaling
    y = _swiglu(h, m["shared_wg"], m["shared_wi"], m["shared_wo"], quant)
    for e in range(Eh):
        gate = jnp.sum(jnp.where(idx == lo + e, w, 0.0), axis=-1)
        y = y + gate[..., None] * _swiglu(h, m["wg"][e], m["w1"][e],
                                          m["w2"][e], quant)
    return x + y, idx


@functools.partial(jax.jit, static_argnums=(0,))
def _head(quant, x, final_norm, lm_head, eps):
    return _mm(_rms(x, final_norm, eps), lm_head, quant)


def _group(w: Dict[str, jnp.ndarray], prefix: str, i=None) -> dict:
    n = len(prefix)
    return {k[n:]: (v if i is None else v[i]) for k, v in w.items()
            if k.startswith(prefix)}


def logits(cfg: dict, w: Dict[str, jnp.ndarray], tokens: np.ndarray,
           quant: Optional[str] = None, with_selections: bool = False):
    """float32 logits ``[R, S, V]`` of token rows ``tokens`` ``[R, S]``;
    with ``with_selections`` also each MoE layer's selected experts
    ``[L - K, R, S, k]``."""
    dims = _dims(cfg)
    L, K = dims[:2]
    eps = jnp.float32(cfg["rms_norm_eps"])
    theta = jnp.float32(cfg["rope_theta"])
    scaling = jnp.float32(cfg["routed_scaling_factor"])
    lo = cfg["expert_parallel"]["held_experts"][0]
    x = w["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    picks = []
    for i in range(L):
        if i < K:
            p = f"prefix.{i}."
            x = _attention(dims, quant, x, _group(w, p + "attn."),
                           w[p + "norm1"], eps, theta)
            x = _dense_mlp(dims, quant, x, _group(w, p + "mlp."),
                           w[p + "norm2"], eps)
            continue
        j = i - K
        x = _attention(dims, quant, x, _group(w, "blocks.attn.", j),
                       w["blocks.norm1"][j], eps, theta)
        x, idx = _moe(dims, quant, lo, x, _group(w, "blocks.moe.", j),
                      w["blocks.norm2"][j], eps, scaling)
        picks.append(idx)
    out = _head(quant, x, w["final_norm"], w["lm_head"], eps)
    return (out, jnp.stack(picks)) if with_selections else out


def position_gaps(ref: np.ndarray, picks: np.ndarray, prompt_len: int
                  ) -> np.ndarray:
    """Per generated position, how far the reference logit of the token
    ``picks`` ``[R, G]`` put there lies below the reference's best at the
    position that produced it.  ``ref`` ``[R, S, V]``."""
    prev = ref[:, prompt_len - 1:-1]
    got = np.take_along_axis(prev, picks[..., None], -1)[..., 0]
    return prev.max(-1) - got


def selections_differ(a: np.ndarray, b: np.ndarray) -> int:
    """Token-layer selections ``[L, R, S, k]`` whose expert sets differ."""
    a, b = np.sort(np.asarray(a), -1), np.sort(np.asarray(b), -1)
    return int((a != b).any(-1).sum())

"""Operations and bytes of a latent-attention MoE decode step (DeepSeek-V3
architecture, one share of the routed experts held), from the published
shapes.

Read from the configuration file (``bench/configs/moonlight-16b-a3b.json``:
Hugging Face key names and ``expert_parallel``), never from the program.
Counts are of what the algorithm needs in one decode step of ``batch``
sequences attending ``positions`` positions:

* latent attention (``attn``): every MLA weight once; the latent and rope
  key of the positions in use read, and the new position written; FLOPs of
  the projections (the absorbed ``W_UK``/``W_UV`` products are ``kv_b``'s
  weights once per token) and of the scores over ``r + dr`` values and the
  weighted sum over ``r`` values a position;
* the feed-forward slot (``mlp``): the held experts', the shared experts'
  and the router's weights once a MoE layer, the dense MLP of the leading
  layers; FLOPs of the tokens routed to held experts (their expected
  number, ``batch * top_k * held / router_width``), the shared experts and
  the router for every token, and the dense MLP;
* the whole step: both, the norms, the batch's embedding rows and the
  output head.
"""
from __future__ import annotations

from typing import Tuple


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


def bytes_per_param(cfg: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]


def mla_layer_params(cfg: dict) -> int:
    """Matmul weights of one layer's latent attention."""
    _, _, d, H, r, dn, dr, dv = _dims(cfg)[:8]
    return d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def latent_bytes_per_token(cfg: dict) -> int:
    """Cache bytes one position of one sequence keeps, all layers."""
    L, _, _, _, r, _, dr = _dims(cfg)[:7]
    return L * (r + dr) * bytes_per_param(cfg)


def mla_step(cfg: dict, batch: int, positions: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of the latent attention of all layers in one step."""
    L, _, _, H, r, _, dr = _dims(cfg)[:7]
    flops = batch * L * (2.0 * mla_layer_params(cfg)
                         + 2.0 * H * (2 * r + dr) * positions)
    nbytes = (L * mla_layer_params(cfg) * bytes_per_param(cfg)
              + batch * (positions + 1) * latent_bytes_per_token(cfg))
    return flops, float(nbytes)


def ffn_step(cfg: dict, batch: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of the feed-forward slot of all layers in one step."""
    L, K, d, _, _, _, _, _, ff, f, E, Eh, ns, top_k, _ = _dims(cfg)
    expert = 3 * d * f
    moe_weights = Eh * expert + ns * expert + d * E
    routed = batch * top_k * Eh / E
    moe_flops = 2.0 * (routed * expert + batch * (ns * expert + d * E))
    flops = (L - K) * moe_flops + K * batch * 2.0 * 3 * d * ff
    nbytes = ((L - K) * moe_weights + K * 3 * d * ff) * bytes_per_param(cfg)
    return flops, float(nbytes)


def decode_step(cfg: dict, batch: int, positions: float
                ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one whole decode step."""
    L, _, d, _, r = _dims(cfg)[:5]
    V = cfg["vocab_size"]
    f_attn, b_attn = mla_step(cfg, batch, positions)
    f_ffn, b_ffn = ffn_step(cfg, batch)
    other = (L * (2 * d + r) + d + d * V) * bytes_per_param(cfg)
    rows = batch * d * bytes_per_param(cfg)
    return (f_attn + f_ffn + batch * 2.0 * d * V,
            b_attn + b_ffn + other + rows)


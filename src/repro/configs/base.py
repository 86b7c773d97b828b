"""Architecture configuration schema.

One frozen dataclass covers all ten assigned families; family-specific
fields default to inert values.  ``reduced()`` derives the smoke-test
configuration (same family, tiny dims) used by per-arch CPU tests; the full
config is exercised only through the dry-run (abstract shapes, no
allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

FAMILIES = ("dense", "moe", "ssm", "hybrid", "audio", "vlm")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: Optional[int] = None            # defaults to d_model // n_heads

    # --- attention flavor ---
    rope_theta: float = 1e4
    rotary_fraction: float = 1.0            # chatglm "RoPE 2d" uses 0.5
    qkv_bias: bool = False                  # qwen2.5
    qk_norm: bool = False                   # chameleon / qwen3
    tie_embeddings: bool = False

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # "softmax": capacity-buffered top-k (moe.moe_apply).  "sigmoid":
    # DeepSeek-V3 noaux_tc routing, dropless, over a held share of the
    # experts (moe.expert_share_apply)
    router: str = "softmax"
    routed_scaling: float = 1.0
    expert_shards: int = 1                  # experts split in equal shares
    expert_shard: int = 0                   # the share this model holds

    # --- latent attention (MLA, DeepSeek-V2/V3): on when kv_lora_rank > 0 ---
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    # --- leading dense layers before the MoE stack (first_k_dense_replace) ---
    first_k_dense: int = 0
    dense_d_ff: int = 0

    # --- SSM (mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_dim: int = 4
    ssm_chunk: int = 128

    # --- hybrid (zamba2): shared attention block every k mamba layers ---
    shared_attn_every: int = 0

    # --- encoder-decoder (whisper) ---
    n_encoder_layers: int = 0
    encoder_seq: int = 1500                 # whisper frame count after conv stub

    # --- activations / norms ---
    activation: str = "silu"
    mlp_gated: bool = True                  # False = 2-matrix MLP (GPT-BigCode)
    norm: str = "rmsnorm"                   # rmsnorm | layernorm
    norm_eps: float = 1e-6

    # --- numerics ---
    param_dtype: str = "bfloat16"
    activation_dtype: str = "bfloat16"

    # --- SPRING profiling (first-class feature) ---
    profile_policy: str = "shortcut"        # off | inline | shortcut
    profile_dtype: str = "float32"

    # --- execution knobs (hillclimb levers) ---
    attn_impl: str = "flash_tri"            # flash_tri | flash_scan | naive
    attn_q_chunk: int = 1024
    attn_kv_chunk: int = 1024
    remat: bool = True
    remat_policy: str = "nothing"           # nothing | dots | full
    scan_layers: bool = True
    loss_chunk: int = 512                   # CE loss seq chunking

    def __post_init__(self):
        if self.family not in FAMILIES:
            raise ValueError(f"family must be one of {FAMILIES}")
        if self.family in ("moe",) and (self.n_experts <= 0 or self.top_k <= 0):
            raise ValueError("moe family needs n_experts and top_k")
        if self.router not in ("softmax", "sigmoid"):
            raise ValueError("router must be softmax or sigmoid")
        if self.expert_shards > 1 and self.router != "sigmoid":
            raise ValueError("only the sigmoid router holds a share of experts")
        if self.n_experts % self.expert_shards or not (
                0 <= self.expert_shard < self.expert_shards):
            raise ValueError("expert shares must split n_experts evenly")
        if bool(self.first_k_dense) != self.mla:
            raise ValueError("MLA is built with leading dense layers "
                             "(the DeepSeek-V3 layout), and only with them")

    # ------------------------------------------------------------------ #
    @property
    def head_dim(self) -> int:
        return self.d_head if self.d_head else self.d_model // self.n_heads

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """Cached values per position and layer under MLA: the normalised
        latent and the rotated shared rope key."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def held_experts(self) -> Tuple[int, int]:
        """[lo, hi) of the routed experts this model holds."""
        n = self.n_experts // self.expert_shards
        return self.expert_shard * n, (self.expert_shard + 1) * n

    def with_expert_share(self, shard: int, shards: int) -> "ModelConfig":
        """This model holding share ``shard`` of ``shards`` of the experts:
        one chip's part of an expert-parallel deployment."""
        return dataclasses.replace(self, expert_shard=shard,
                                   expert_shards=shards)

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to 256 so the vocab axis shards cleanly
        (Megatron-style padding; padded logits are masked in the loss)."""
        return (self.vocab_size + 255) // 256 * 256

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def is_encdec(self) -> bool:
        return self.n_encoder_layers > 0

    @property
    def supports_long_context(self) -> bool:
        """True if decode at 500k+ context is sub-quadratic / O(1)-state."""
        return self.family in ("ssm", "hybrid")

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def dtype(self):
        return jnp.dtype(self.param_dtype)

    # approximate parameter count (analytic; used for MODEL_FLOPS = 6·N·D)
    def param_count(self, active_only: bool = False) -> int:
        d, f, v, L = self.d_model, self.d_ff, self.vocab_size, self.n_layers
        dh, H, KV = self.head_dim, self.n_heads, self.n_kv_heads
        embed = v * d * (1 if self.tie_embeddings else 2)
        attn = d * H * dh + 2 * d * KV * dh + H * dh * d
        if self.mla:
            r, dn, dr, dv = (self.kv_lora_rank, self.qk_nope_dim,
                             self.qk_rope_dim, self.v_head_dim)
            attn = (d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv)
                    + H * dv * d)
        if self.family == "ssm":
            per_layer = self._mamba_params()
            return embed + L * per_layer
        mlp3 = (3 if self.mlp_gated else 2) * d * f
        if self.family == "moe":
            lo, hi = self.held_experts
            ff_all = (hi - lo) * mlp3 + d * self.n_experts
            ff_act = self.top_k * mlp3 + d * self.n_experts
            if self.n_shared_experts:
                shared = self.n_shared_experts * mlp3
                ff_all += shared
                ff_act += shared
            per_layer = attn + (ff_act if active_only else ff_all)
            k = self.first_k_dense
            dense = attn + 3 * d * self.dense_d_ff
            return embed + (L - k) * per_layer + k * dense
        if self.family == "hybrid":
            mamba = self._mamba_params()
            n_attn = (L // self.shared_attn_every) if self.shared_attn_every else 0
            shared_blk = attn + mlp3  # one parameter set, reused
            return embed + L * mamba + shared_blk
        per_layer = attn + mlp3
        return embed + L * per_layer

    def _mamba_params(self) -> int:
        d, di, n = self.d_model, self.d_inner, self.ssm_state
        h = self.ssm_heads
        in_proj = d * (2 * di + 2 * n + h)
        conv = self.ssm_conv_dim * (di + 2 * n)
        out = di * d
        return in_proj + conv + out + 3 * h  # A, D, dt_bias

    # ------------------------------------------------------------------ #
    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        small = dict(
            n_layers=2,
            d_model=64,
            n_heads=4,
            n_kv_heads=max(1, min(self.n_kv_heads, 2)),
            d_head=16,
            d_ff=128,
            vocab_size=256,
            encoder_seq=16,
            attn_q_chunk=8,
            attn_kv_chunk=8,
            loss_chunk=8,
            ssm_head_dim=16,
            ssm_state=16 if self.ssm_state else 0,
            ssm_chunk=8,
            scan_layers=self.scan_layers,
        )
        if self.n_experts:
            small.update(n_experts=4, top_k=2)
        if self.router == "sigmoid":
            # enough experts for eight shares of two, published top-6
            small.update(n_experts=16, top_k=6)
        if self.mla:
            small.update(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                         v_head_dim=16)
        if self.first_k_dense:
            small.update(n_layers=self.first_k_dense + 2, dense_d_ff=192)
        if self.n_encoder_layers:
            small.update(n_encoder_layers=2)
        if self.shared_attn_every:
            small.update(shared_attn_every=2)
        return dataclasses.replace(self, **small)


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    """One assigned (input-shape) cell."""

    name: str            # train_4k | prefill_32k | decode_32k | long_500k
    kind: str            # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPE_CELLS = (
    ShapeCell("train_4k", "train", 4_096, 256),
    ShapeCell("prefill_32k", "prefill", 32_768, 32),
    ShapeCell("decode_32k", "decode", 32_768, 128),
    ShapeCell("long_500k", "decode", 524_288, 1),
)


def cell_by_name(name: str) -> ShapeCell:
    for c in SHAPE_CELLS:
        if c.name == name:
            return c
    raise KeyError(name)


def cell_applicable(cfg: ModelConfig, cell: ShapeCell) -> Tuple[bool, str]:
    """Whether an (arch × shape) cell runs, per the assignment rules."""
    if cell.name == "long_500k" and not cfg.supports_long_context:
        return False, "full-attention arch: 500k decode needs sub-quadratic attention"
    return True, ""

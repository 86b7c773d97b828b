"""Operations and bytes a configuration's work needs, from its published shapes.

Computed from the configuration file (``bench/configs/*.json``, Hugging Face
key names), never from the program's own parameter tree, so a change to the
program cannot move the yardstick.  Counts are of what the algorithm needs:
a decode step reads every weight matrix once, the embedding rows of its
tokens, and the keys and values of the positions in use, not of the whole
allocated cache.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    kv = cfg["multi_query_group_num"]
    dh = cfg["kv_channels"]
    return (cfg["num_layers"], d, heads, kv, dh, cfg["ffn_hidden_size"],
            cfg["padded_vocab_size"])


def bytes_per_param(cfg: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one decoder layer that take part in a matmul."""
    _, d, heads, kv, dh, ff, _ = _dims(cfg)
    attn = d * heads * dh + 2 * d * kv * dh + heads * dh * d
    return attn + 3 * d * ff          # gated MLP: gate, up, down


def param_count(cfg: dict) -> int:
    """Every weight: embedding, layers with their two norms, final norm,
    output head."""
    layers, d, _, _, _, _, vocab = _dims(cfg)
    head = 0 if cfg.get("tie_word_embeddings") else vocab * d
    return vocab * d + layers * (layer_matmul_params(cfg) + 2 * d) + d + head


def weight_bytes(cfg: dict) -> int:
    return param_count(cfg) * bytes_per_param(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """Key and value bytes one position of one sequence keeps, all layers."""
    layers, _, _, kv, dh, _, _ = _dims(cfg)
    return layers * 2 * kv * dh * bytes_per_param(cfg)


def decode_step_flops(cfg: dict, batch: int, positions: float) -> float:
    """Model FLOPs of one decode step: 2 per matmul weight per sequence,
    plus QK^T and PV over ``positions`` keys in every layer."""
    layers, d, heads, _, dh, _, vocab = _dims(cfg)
    matmul = layers * layer_matmul_params(cfg) + d * vocab
    attention = layers * 4 * heads * dh * positions
    return batch * (2.0 * matmul + attention)


def decode_step_bytes(cfg: dict, batch: int, positions: float) -> float:
    """HBM bytes one decode step needs: every weight but the embedding
    table once, the batch's embedding rows, the keys and values of
    ``positions`` positions per sequence, and the new position written."""
    layers, d, _, _, _, _, vocab = _dims(cfg)
    bpp = bytes_per_param(cfg)
    weights = weight_bytes(cfg) - vocab * d * bpp + batch * d * bpp
    kv = batch * (positions + 1) * kv_bytes_per_token(cfg)
    return float(weights + kv)


def mean_decode_positions(prompt_len: int, gen: int) -> float:
    """Mean keys attended over the timed decode steps.

    ``run_serve`` times generated steps 2..gen; the step at cache position
    ``pos`` attends ``pos + 1`` keys, and those steps sit at positions
    ``prompt_len`` .. ``prompt_len + gen - 2``.
    """
    first, last = prompt_len + 1, prompt_len + gen - 1
    return (first + last) / 2.0

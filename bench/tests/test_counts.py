"""Counts of chatglm3-6b's work against hand-worked values, and the peaks."""
import json
from pathlib import Path

import pytest

from bench import counts

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs"
                  / "chatglm3-6b.json").read_text())


def test_weight_bytes_hand_worked():
    # per layer: wq 4096*4096 + wk, wv 4096*256 each + wo 4096*4096
    # + 3 * 4096 * 13696 + two norms of 4096 = 203,956,224 parameters;
    # 28 layers + embedding and head 65024*4096 each + final norm 4096
    per_layer = 16_777_216 + 2 * 1_048_576 + 16_777_216 + 168_296_448 + 8_192
    assert per_layer == 203_956_224
    params = 28 * per_layer + 2 * 266_338_304 + 4096
    assert counts.param_count(CFG) == params == 6_243_454_976
    assert counts.weight_bytes(CFG) == 12_486_909_952      # 12.49 GB


def test_kv_bytes_per_token():
    # 28 layers x (K and V) x 2 KV heads x 128 dims x 2 bytes
    assert counts.kv_bytes_per_token(CFG) == 28_672


def test_decode_step_counts():
    batch, pos = 32, 384.0
    matmul = 28 * (203_956_224 - 8_192) + 266_338_304
    attention = 28 * 4 * 32 * 128 * pos
    assert counts.decode_step_flops(CFG, batch, pos) == pytest.approx(
        batch * (2 * matmul + attention))
    # every weight but the embedding table, 32 embedding rows, and the
    # keys and values of pos positions plus the one written
    want = (12_486_909_952 - 266_338_304 * 2 + batch * 4096 * 2
            + batch * (pos + 1) * 28_672)
    assert counts.decode_step_bytes(CFG, batch, pos) == pytest.approx(want)
    assert counts.mean_decode_positions(128, 512) == pytest.approx(384.0)


def test_peaks():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError):
        counts.peaks("cpu")

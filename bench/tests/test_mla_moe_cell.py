"""The Moonlight cell at a tiny size: the harness's own path reads it
correct, its readers read a traced window, its counts are the hand-worked
ones, and the float8 control reads far above the program."""
import json
import types
from pathlib import Path

import numpy as np
import pytest

from bench import counts_mla_moe, run
from bench.reference import mla_moe
from bench.tests._drive import TINY_DECODE, run_cell
from bench.tests.test_program_metrics import HLO, serve_trace

BENCH = Path(__file__).resolve().parents[1]
CFG = json.loads((BENCH / "configs" / "moonlight-16b-a3b.json").read_text())
# the program's reduced preset holding one share of 8: 2 of 16 experts
TINY_MOONLIGHT = {
    "num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 192, "moe_intermediate_size": 128,
    "n_routed_experts": 2, "vocab_size": 256,
    "expert_parallel": {"chips": 8, "router_width": 16,
                        "held_experts": [0, 2]},
    "program": {"arch": "moonlight-16b-a3b", "reduced": True,
                "expert_shard": 0, "expert_shards": 8}}
TINY = {**CFG, **TINY_MOONLIGHT}
NEW_READERS = ("mla_decode_ms", "moe_decode_ms", "mla_moe_unscoped_ms",
               "mla_decode_roofline", "moe_decode_roofline",
               "mla_moe_step_roofline", "mla_moe_mfu_pct")


def test_cell_is_correct_at_a_tiny_size():
    out = run_cell("moonlight.decode.inline", config=TINY_MOONLIGHT,
                   traffic=TINY_DECODE)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"decode_step_ms", "ttft_ms", "setup_s"}


def test_program_config_refuses_another_model():
    from bench.drivers.serve_mla_moe import program_config

    cfg = program_config(TINY)
    assert cfg.held_experts == (0, 2) and cfg.n_experts == 16
    for key, value in (("hidden_size", 128), ("n_routed_experts", 4),
                       ("scoring_func", "softmax")):
        with pytest.raises(RuntimeError):
            program_config({**TINY, key: value})


@pytest.mark.parametrize("name", NEW_READERS + (
    "decode_device_ms", "device_idle_pct.serve", "profile_host_ms",
    "profile_programs_per_step"))
def test_reader_reads_a_traced_window(monkeypatch, name):
    from bench.metrics import _mla_moe

    monkeypatch.setattr(_mla_moe, "compiled_step_text", lambda records: HLO)
    trace = serve_trace()
    records = {"program": "serve_step", "batch": 2, "prompt_len": 4,
               "gen": 3, "config": TINY}
    ctx = types.SimpleNamespace(
        trace=trace, records=records, driver_window=lambda t: (20, 60),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    value = run.load_module(run.BENCH / "metrics" / f"{name}.py").read(
        ctx, records)
    assert isinstance(value, float) and value > 0, value
    # the synthetic steps: attn 3 ns, mlp 3.5 ns, unscoped 3.5 ns a step
    want = {"mla_decode_ms": 3e-6, "moe_decode_ms": 3.5e-6,
            "mla_moe_unscoped_ms": 3.5e-6}
    if name in want:
        assert value == pytest.approx(want[name])


def test_counts_hand_worked():
    # MLA per layer: q 2048 x 16 x 192, kv_a 2048 x 576, kv_b 512 x 16 x
    # 256, o 16 x 128 x 2048
    assert counts_mla_moe.mla_layer_params(CFG) == (
        6_291_456 + 1_179_648 + 2_097_152 + 4_194_304) == 13_762_560
    assert counts_mla_moe.latent_bytes_per_token(CFG) == 27 * 576 * 2
    expert = 3 * 2048 * 1408
    batch, pos = 128, 384.0
    f, b = counts_mla_moe.ffn_step(CFG, batch)
    assert b == 2 * (26 * (10 * expert + 2048 * 64) + 3 * 2048 * 11264)
    routed = batch * 6 * 8 / 64               # 12 tokens a held expert
    assert f == pytest.approx(26 * 2 * (routed * expert + batch * (
        2 * expert + 2048 * 64)) + batch * 2 * 3 * 2048 * 11264)
    f, b = counts_mla_moe.mla_step(CFG, batch, pos)
    assert b == 27 * 13_762_560 * 2 + batch * (pos + 1) * 27 * 576 * 2
    # and the norms, the head 2048 x 163840 and 128 embedding rows
    f, b = counts_mla_moe.decode_step(CFG, batch, pos)
    assert b / 819e9 == pytest.approx(9.27e-3, rel=1e-3)


@pytest.mark.parametrize("seed", [5, 2**31 + 17, 123456])
def test_fp8_control_reads_far_above_the_program(seed):
    """At this size the readings are smaller than at the cell's; the test
    holds the control to at least three times the program on every seed,
    the separation the cell's limit needs."""
    from bench.drivers.serve_mla_moe import program_config
    from repro.launch.serve import run_serve

    seed %= 2**31 - 1024
    prompt_len = 16
    res = run_serve(program_config(TINY), batch=2, prompt_len=prompt_len,
                    gen=32, seed=seed, profile_policy="off")
    toks = np.asarray(res.tokens)
    w = mla_moe.make_weights(TINY, seed)
    ref = np.asarray(mla_moe.logits(TINY, w, toks))
    ctrl = np.asarray(mla_moe.logits(TINY, w, toks, quant="fp8"))
    # the cell's statistic: the mean gap over the generated positions
    P = prompt_len
    program = mla_moe.position_gaps(ref, toks[:, P:], P).mean()
    control = mla_moe.position_gaps(ref, ctrl[:, P - 1:-1].argmax(-1),
                                    P).mean()
    assert control >= 3 * program, (program, control)

"""moonlight-16b-a3b against its plain float32 reference at a small size.

The reference (``bench/reference/mla_moe.py``) decompresses per-head keys
and values and runs one whole forward with no cache; the program decodes
through its latent cache with the absorbed products.  Both run in float32
on the same seeded weights, so what separates them is float32 rounding in a
different order of operations: the tolerances below are for that alone.
"""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import mla_moe
from repro.configs import get_config
from repro.models import init_params
from repro.models.api import decode_fn, init_caches, model_specs, prefill_fn
from repro.models.moe import expert_share_apply, sigmoid_route
from repro.models.transformer import assemble_stream

ROOT = Path(__file__).resolve().parents[1]
# the reduced preset as the reference reads it (Hugging Face key names)
SMALL = {"num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "intermediate_size": 192,
         "moe_intermediate_size": 128, "vocab_size": 256,
         "torch_dtype": "float32"}
# float32 on both sides: rounding of a different order of operations only
TOL = dict(rtol=1e-4, atol=1e-4)


def configs(shard=0, shards=1):
    """(program config, reference config) holding share ``shard``."""
    cfg = dataclasses.replace(
        get_config("moonlight-16b-a3b").reduced(), param_dtype="float32",
        activation_dtype="float32", attn_impl="naive"
    ).with_expert_share(shard, shards)
    lo, hi = cfg.held_experts
    ref = {**json.loads((ROOT / "bench" / "configs"
                         / "moonlight-16b-a3b.json").read_text()), **SMALL,
           "n_routed_experts": hi - lo,
           "expert_parallel": {"router_width": cfg.n_experts,
                               "held_experts": [lo, hi]}}
    return cfg, ref


def setup(seed, shards=1, tokens=(2, 12)):
    cfg, ref = configs(0, shards)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(seed))
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(seed + 1),
                                         tokens, 0, cfg.vocab_size))
    want = np.asarray(mla_moe.logits(ref, mla_moe.make_weights(ref, seed),
                                     toks))
    return cfg, params, toks, want


def test_one_chip_share_holds_3_36_b_of_15_96_b_parameters():
    cfg = get_config("moonlight-16b-a3b")
    # 8 of 64 experts in each of 26 MoE layers; everything else whole
    expert = 3 * 2048 * 1408
    assert cfg.param_count() - cfg.with_expert_share(3, 8).param_count() == (
        26 * 56 * expert)
    assert round(cfg.with_expert_share(0, 8).param_count() / 1e9, 2) == 3.36


def test_reference_weights_are_the_program_weights():
    cfg, ref = configs(0, 8)
    prog = init_params(model_specs(cfg), jax.random.PRNGKey(3))
    w = mla_moe.make_weights(ref, 3)
    flat = {"blocks.attn.wkv_b": prog["blocks"]["attn"]["wkv_b"],
            "blocks.moe.router_bias": prog["blocks"]["moe"]["router_bias"],
            "blocks.moe.w2": prog["blocks"]["moe"]["w2"],
            "prefix.0.mlp.wi": prog["prefix"][0]["mlp"]["wi"],
            "prefix.0.attn.wq": prog["prefix"][0]["attn"]["wq"],
            "lm_head": prog["lm_head"]}
    assert [s for _, s, _ in mla_moe.leaf_table(ref)] == [
        v.shape for v in jax.tree_util.tree_leaves(prog)]
    for name, value in flat.items():
        assert value.shape == w[name].shape, name
        np.testing.assert_array_equal(np.asarray(value), np.asarray(w[name]))


@pytest.mark.parametrize("seed,shards", [(0, 1), (11, 8)])
def test_absorbed_decode_matches_the_plain_reference(seed, shards):
    cfg, params, toks, want = setup(seed, shards)
    B, T = toks.shape
    caches = init_caches(cfg, B, T)
    # a row holds the latent and the rope key, padded to 128 lanes
    assert caches.blocks.shape[-1] == -(-(cfg.kv_lora_rank
                                         + cfg.qk_rope_dim) // 128) * 128
    got = []
    for t in range(T):
        lg, caches, _ = decode_fn(cfg, params, caches, toks[:, t:t + 1], t)
        got.append(lg[:, 0, :cfg.vocab_size])
    np.testing.assert_allclose(np.stack(got, 1), want, **TOL)


def test_prefill_then_decode_matches_the_plain_reference():
    cfg, params, toks, want = setup(5, 8)
    B, T = toks.shape
    S = 7
    last, caches = prefill_fn(cfg, params, {"tokens": toks[:, :S]})
    np.testing.assert_allclose(last[:, 0, :cfg.vocab_size], want[:, S - 1],
                               **TOL)
    pad = [(0, 0), (0, 0), (0, T - S), (0, 0)]
    caches = jax.tree_util.tree_map(lambda c: jnp.pad(c, pad), caches)
    for t in range(S, T):
        lg, caches, _ = decode_fn(cfg, params, caches, toks[:, t:t + 1], t)
        np.testing.assert_allclose(lg[:, 0, :cfg.vocab_size], want[:, t],
                                   **TOL)


def _layer_inputs(seed):
    """One MoE layer's weights over all 16 experts (reference names), an
    input ``x`` and the layer's normalised input ``h``."""
    cfg, ref = configs()
    w = mla_moe.make_weights(ref, seed)
    m = {k[len("blocks.moe."):]: v[0] for k, v in w.items()
         if k.startswith("blocks.moe.")}
    x = jax.random.normal(jax.random.PRNGKey(seed), (2, 9, 64), jnp.float32)
    return cfg, ref, m, x, mla_moe._rms(x, jnp.ones(64), 0.0)


def _reference_part(ref, m, x, lo, hi, shared=True):
    """The reference MoE layer's output (its residual taken off) from
    experts [lo, hi), with or without the shared experts."""
    if not shared:
        m = {**m, "shared_wo": jnp.zeros_like(m["shared_wo"])}
    m = {**m, "w1": m["w1"][lo:hi], "wg": m["wg"][lo:hi],
         "w2": m["w2"][lo:hi]}
    one = {**ref, "expert_parallel": {"router_width": 16,
                                      "held_experts": [lo, hi]}}
    y, _ = mla_moe._moe(mla_moe._dims(one), None, lo, x, m, jnp.ones(64),
                        jnp.float32(0.0),
                        jnp.float32(ref["routed_scaling_factor"]))
    return np.asarray(y - x)


def _program_share(cfg, m, h, r):
    """The program's layer holding share ``r`` of 8: (y, profile)."""
    c = cfg.with_expert_share(r, 8)
    lo, hi = c.held_experts
    p = {**m, "w1": m["w1"][lo:hi], "wg": m["wg"][lo:hi],
         "w2": m["w2"][lo:hi]}
    y, _, prof = expert_share_apply(p, h, top_k=c.top_k,
                                    scaling=c.routed_scaling, held=(lo, hi),
                                    activation="silu")
    return np.asarray(y), prof


def _shared(m, h):
    hs = jax.nn.silu(h @ m["shared_wg"]) * (h @ m["shared_wi"])
    return np.asarray(hs @ m["shared_wo"])


def test_expert_shares_sum_to_the_uncut_reference_layer():
    cfg, ref, m, x, h = _layer_inputs(2)
    want = _reference_part(ref, m, x, 0, 16)
    shared = _shared(m, h)
    # each share computes the shared experts alike: count them once
    parts = [_program_share(cfg, m, h, r)[0] - shared for r in range(8)]
    np.testing.assert_allclose(sum(parts) + shared, want, **TOL)


def test_correction_bias_moves_selection_not_weights():
    cfg, _, m, _, h = _layer_inputs(4)
    no_bias = {**m, "router_bias": jnp.zeros_like(m["router_bias"])}
    scores, idx0, w0 = sigmoid_route(no_bias, h, cfg.top_k, cfg.routed_scaling)
    # push expert 3 into every selection
    push = {**m, "router_bias": m["router_bias"].at[3].set(10.0)}
    _, idx1, w1 = sigmoid_route(push, h, cfg.top_k, cfg.routed_scaling)
    assert (idx1 == 3).any(-1).all()
    assert (np.sort(idx0, -1) != np.sort(idx1, -1)).any()
    # the weights are the selected scores without the bias
    sel = jnp.take_along_axis(scores, idx1, axis=-1)
    np.testing.assert_allclose(
        w1, sel / sel.sum(-1, keepdims=True) * cfg.routed_scaling, rtol=1e-6)
    np.testing.assert_allclose(w0.sum(-1), cfg.routed_scaling, rtol=1e-6)
    assert float(jnp.max(w1)) < cfg.routed_scaling


def test_no_token_dropped_under_skewed_routing():
    cfg, ref, m, x, h = _layer_inputs(6)
    # every token routed to experts 0 and 1, the share held here
    skew = {**m, "router_bias": m["router_bias"].at[:2].set(10.0)}
    y, prof = _program_share(cfg, skew, h, 0)
    np.testing.assert_array_equal(prof["expert_tokens"], [18.0, 18.0])
    np.testing.assert_allclose(prof["unheld_share"], [1 - 2 / 6], rtol=1e-6)
    # the uncut layer less the other shares' routed parts
    want = _reference_part(ref, skew, x, 0, 16) - sum(
        _reference_part(ref, skew, x, 2 * r, 2 * r + 2, shared=False)
        for r in range(1, 8))
    np.testing.assert_allclose(y, want, **TOL)


def test_moe_tap_reports_tokens_per_held_expert():
    cfg, params, toks, _ = setup(9, 8)
    caches = init_caches(cfg, 2, 4)
    _, _, rows = decode_fn(cfg, params, caches, toks[:, :1], 0)
    d = assemble_stream(cfg, rows).decode()
    # the dense layer 0 has no experts: its words are placeholders
    assert (d["block0/expert_tokens"] == -1).all()
    for i in range(1, cfg.n_layers):
        tokens = d[f"block{i}/expert_tokens"]
        assert tokens.shape == (2,)
        unheld = float(d[f"block{i}/unheld_share"][0])
        assert tokens.sum() == pytest.approx(2 * cfg.top_k * (1 - unheld))


def test_decode_step_scopes():
    """Latent attention under ``attn`` (its cache write ``latent_update``),
    the whole feed-forward slot under ``mlp`` with the MoE layer's
    ``router``, ``experts`` and ``shared_experts``."""
    import re

    from bench.metrics import _scopes
    from repro.train.step import make_serve_step

    cfg = get_config("moonlight-16b-a3b").reduced().with_expert_share(0, 8)
    specs = model_specs(cfg)
    params = jax.eval_shape(lambda: init_params(specs, jax.random.PRNGKey(0)))
    caches = jax.eval_shape(lambda: init_caches(cfg, 2, 8))
    tokens = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    lowered = jax.jit(make_serve_step(cfg)).lower(params, caches, tokens, 0)
    text = lowered.as_text(debug_info=True)
    for path in ("attn/latent_update", "mlp/router", "mlp/experts",
                 "mlp/shared_experts", "mlp/dot_general"):
        assert re.search(rf'loc\("([^"]*/)?{path}', text), path
    scopes = _scopes.instruction_scopes(lowered.compile().as_text())
    assert {"attn", "mlp", "norm", "embed", "logits"} <= set(scopes.values())

"""Model-zoo correctness: attention paths, SSD, MoE, decode consistency."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.models import init_params
from repro.models.attention import (
    flash_scan_attention, flash_tri_attention, naive_attention,
)
from repro.models.moe import capacity_for, moe_apply, moe_specs
from repro.models.ssm import ssd_chunked, ssd_reference
from repro.models.api import init_caches, model_specs
from repro.models.transformer import (
    _rows, assemble_stream, block_apply_decode, kv_cache_init, lm_decode_step,
    lm_loss, lm_specs, ssm_caches_init, tape_spec_for)
from repro.models.common import rms_norm


def rand(key, *shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype) * 0.5


# --------------------------------------------------------------------- #
# attention
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("t,h,kv,dh", [(32, 4, 4, 16), (64, 8, 2, 8),
                                       (48, 6, 1, 32)])
def test_flash_tri_matches_naive(t, h, kv, dh):
    q, k, v = rand(0, 2, t, h, dh), rand(1, 2, t, kv, dh), rand(2, 2, t, kv, dh)
    ref, lref = naive_attention(q, k, v, causal=True)
    out, lmax = flash_tri_attention(q, k, v, q_chunk=16, kv_chunk=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert float(lmax) == pytest.approx(float(lref), rel=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_scan_matches_naive(causal):
    t, s, h, kv, dh = 16, 64, 4, 2, 16
    q, k, v = rand(3, 2, t, h, dh), rand(4, 2, s, kv, dh), rand(5, 2, s, kv, dh)
    # cross/self with offset: q positions start at s - t
    ref, _ = naive_attention(q, k, v, causal=causal, q_offset=s - t)
    out, _ = flash_scan_attention(q, k, v, causal=causal, q_offset=s - t,
                                  kv_chunk=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@settings(deadline=None, max_examples=10)
@given(st.integers(1, 4), st.integers(1, 3), st.integers(2, 5))
def test_property_flash_tri_gqa_groups(b, kv_mult, chunk_pow):
    t, kv, dh = 32, 2, 8
    h = kv * kv_mult
    q, k, v = rand(6, b, t, h, dh), rand(7, b, t, kv, dh), rand(8, b, t, kv, dh)
    ref, _ = naive_attention(q, k, v, causal=True)
    out, _ = flash_tri_attention(q, k, v, q_chunk=2 ** chunk_pow,
                                 kv_chunk=2 ** chunk_pow)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


# --------------------------------------------------------------------- #
# SSD (mamba2)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("t,chunk", [(32, 8), (64, 16), (24, 24)])
def test_ssd_chunked_matches_sequential(t, chunk):
    b, h, p, n = 2, 3, 8, 4
    x = rand(10, b, t, h, p)
    dt = jax.nn.softplus(rand(11, b, t, h))
    A = -jnp.exp(rand(12, h) * 0.5)
    Bm, Cm = rand(13, b, t, n), rand(14, b, t, n)
    y_ref, s_ref = ssd_reference(x, dt, A, Bm, Cm)
    y, s = ssd_chunked(x, dt, A, Bm, Cm, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-4)


def test_ssd_initial_state_carries():
    b, t, h, p, n = 1, 16, 2, 4, 4
    x = rand(20, b, t, h, p)
    dt = jax.nn.softplus(rand(21, b, t, h))
    A = -jnp.exp(rand(22, h) * 0.5)
    Bm, Cm = rand(23, b, t, n), rand(24, b, t, n)
    # full run == two half runs with state carried
    y_full, s_full = ssd_chunked(x, dt, A, Bm, Cm, 8)
    y1, s1 = ssd_chunked(x[:, :8], dt[:, :8], A, Bm[:, :8], Cm[:, :8], 8)
    y2, s2 = ssd_chunked(x[:, 8:], dt[:, 8:], A, Bm[:, 8:], Cm[:, 8:], 8,
                         init_state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------- #
# MoE
# --------------------------------------------------------------------- #
def dense_moe_reference(p, x, top_k, activation="silu"):
    """Loop-over-experts oracle (no capacity)."""
    from repro.models.common import ACTIVATIONS
    act = ACTIVATIONS[activation]
    logits = (x @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_e = jax.lax.top_k(probs, top_k)
    topk_w = topk_w / jnp.sum(topk_w, axis=-1, keepdims=True)
    E = p["router"].shape[-1]
    y = jnp.zeros_like(x)
    for e in range(E):
        he = act(x @ p["wg"][e]) * (x @ p["w1"][e])
        ye = he @ p["w2"][e]
        w_e = jnp.sum(jnp.where(topk_e == e, topk_w, 0.0), axis=-1)
        y = y + ye * w_e[..., None].astype(ye.dtype)
    return y


def test_moe_matches_dense_reference_with_ample_capacity():
    B, S, d, f, E, k = 1, 16, 8, 16, 4, 2
    specs = moe_specs(d, f, E, jnp.float32)
    p = init_params(specs, jax.random.PRNGKey(0))
    x = rand(30, B, S, d)
    y, aux, prof = moe_apply(p, x, top_k=k, capacity_factor=float(E),
                             activation="silu")
    y_ref = dense_moe_reference(p, x, k)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    assert float(jnp.sum(prof["expert_overflow"])) == 0.0
    # conservation: every routed assignment is in some buffer (B=1)
    assert float(jnp.sum(prof["expert_fullness"])) == S * k


def test_moe_capacity_drops_tokens_and_reports_overflow():
    B, S, d, f, E, k = 1, 32, 8, 16, 4, 2
    specs = moe_specs(d, f, E, jnp.float32)
    p = dict(init_params(specs, jax.random.PRNGKey(1)))
    # skew the router so expert 0 is hot: positive inputs + biased column
    p["router"] = p["router"].at[:, 0].add(10.0)
    x = jnp.abs(rand(31, B, S, d)) + 0.1
    cap = capacity_for(S, k, E, 1.0)
    y, aux, prof = moe_apply(p, x, top_k=k, capacity_factor=1.0,
                             activation="silu")
    assert float(prof["expert_fullness"][0]) == cap      # buffer runs full
    assert float(prof["expert_overflow"][0]) > 0         # and overflows
    assert not bool(jnp.isnan(y).any())
    # fullness + overflow conserves all S*k assignments (B=1)
    total = float(jnp.sum(prof["expert_fullness"] + prof["expert_overflow"]))
    assert total == S * k


def test_moe_aux_loss_penalizes_imbalance():
    B, S, d, f, E, k = 2, 64, 8, 16, 4, 1
    specs = moe_specs(d, f, E, jnp.float32)
    p_bal = init_params(specs, jax.random.PRNGKey(2))
    p_skew = dict(p_bal)
    p_skew["router"] = p_bal["router"].at[:, 0].add(10.0)
    x = rand(32, B, S, d)
    _, aux_bal, _ = moe_apply(p_bal, x, top_k=k, capacity_factor=2.0,
                              activation="silu")
    _, aux_skew, _ = moe_apply(p_skew, x, top_k=k, capacity_factor=2.0,
                               activation="silu")
    assert float(aux_skew) > float(aux_bal)


# --------------------------------------------------------------------- #
# decode == teacher-forced forward (the serving-correctness invariant)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("family,extra", [
    ("dense", {}),
    ("moe", dict(n_experts=4, top_k=2, capacity_factor=8.0)),
    ("ssm", dict(ssm_state=16)),
])
def test_decode_matches_prefill_logits(family, extra):
    cfg = ModelConfig(
        name=f"{family}-dec", family=family, n_layers=2, d_model=32,
        n_heads=4, n_kv_heads=2 if family != "ssm" else 4, d_head=8,
        d_ff=64, vocab_size=64, attn_impl="naive", scan_layers=True,
        loss_chunk=4, ssm_chunk=4, ssm_head_dim=8,
        param_dtype="float32", activation_dtype="float32", **extra)
    params = init_params(lm_specs(cfg), jax.random.PRNGKey(0))
    T = 8
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, T), 0, 64)

    # teacher-forced logits at each position
    from repro.models.transformer import lm_hidden, lm_logits
    positions = jnp.arange(T)[None, :]
    h, _, _ = lm_hidden(cfg, params, toks, positions)
    full_logits = lm_logits(cfg, params, h)

    # token-by-token decode
    if family == "ssm":
        caches = ssm_caches_init(cfg, 1)
    else:
        caches = kv_cache_init(cfg, 1, T)
    outs = []
    for t in range(T):
        lg, caches, _ = lm_decode_step(cfg, params, caches, toks[:, t:t+1], t)
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec_logits),
                               np.asarray(full_logits), rtol=2e-3, atol=2e-3)


_block = jax.jit(block_apply_decode, static_argnums=0)


@functools.partial(jax.jit, static_argnums=0)
def _head(cfg, params, x):
    from repro.models.transformer import lm_logits
    return lm_logits(cfg, params,
                     rms_norm(x, params["final_norm"], cfg.norm_eps))


def _take(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def _put(tree, i, new):
    return jax.tree_util.tree_map(lambda a, n: a.at[i].set(n), tree, new)


def _per_layer_decode(cfg, params, caches, tokens, pos):
    """``lm_decode_step`` as a Python loop over layers: each layer's
    weights and cache slice taken out by indexing, the block applied to
    that one layer (one program a layer, so that layer outputs are rounded
    to their dtype as the scan's carry is), and the slice put back by
    indexing."""
    spec, pdtype = tape_spec_for(cfg), jnp.dtype(cfg.profile_dtype)
    x = params["embed"][tokens].astype(jnp.dtype(cfg.activation_dtype))
    if cfg.family == "ssm":
        stacks, tapes = caches, []
        for i in range(cfg.n_layers):
            x, new, tape = _block(cfg, _take(params["blocks"], i), x,
                                  _take(stacks, i), None, pos)
            stacks = _put(stacks, i, new)
            tapes.append(tape)
        new_caches = stacks
    else:
        stacks = ([caches.prefix, caches.blocks] if cfg.mla
                  else [(caches.k, caches.v)])
        layers = ([(0, p) for p in params.get("prefix", ())]
                  + [(len(stacks) - 1, _take(params["blocks"], i))
                     for i in range(cfg.n_layers - cfg.first_k_dense)])
        tapes, seen = [], [0] * len(stacks)
        for which, p_l in layers:
            i = seen[which]
            one = jax.tree_util.tree_map(lambda a: a[i][None], stacks[which])
            x, one, tape = _block(cfg, p_l, x, one, 0, pos)
            stacks[which] = _put(stacks[which], i, _take(one, 0))
            seen[which] += 1
            tapes.append(tape)
        new_caches = type(caches)(*(stacks if cfg.mla else stacks[0]))
    rows = _rows(cfg, spec, tapes, pdtype, cfg.profile_policy)
    return _head(cfg, params, x), new_caches, rows


DECODE_ARCHS = {
    "dense": lambda: get_config("chatglm3-6b").reduced(),
    "softmax-moe": lambda: get_config("qwen3-moe-235b-a22b").reduced(),
    "mla-share": lambda: get_config(
        "moonlight-16b-a3b").reduced().with_expert_share(0, 8),
    "ssm": lambda: get_config("mamba2-780m").reduced(),
}


@pytest.mark.parametrize("policy", ["off", "shortcut", "inline"])
@pytest.mark.parametrize("arch", sorted(DECODE_ARCHS))
def test_decode_step_matches_per_layer_reference(arch, policy):
    """The scanned step, carrying the stacked caches and writing one
    position a layer in place, equals a loop over layers that updates each
    layer's slice by indexing: logits, every cache tensor and the profile
    rows bit for bit, at the first, a middle and the last position; the
    caches change at that position alone."""
    cfg = dataclasses.replace(DECODE_ARCHS[arch](), profile_policy=policy)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    B, S = 3, 9
    zeros = init_caches(cfg, B, S)
    keys = jax.random.split(jax.random.PRNGKey(1),
                            len(jax.tree_util.tree_leaves(zeros)))
    caches = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(zeros),
        [jax.random.normal(k, a.shape).astype(a.dtype) for k, a in
         zip(keys, jax.tree_util.tree_leaves(zeros))])
    step = jax.jit(lambda c, t, p: lm_decode_step(cfg, params, c, t, p))
    for pos in (0, S // 2, S - 1):
        toks = jax.random.randint(jax.random.PRNGKey(pos), (B, 1), 0,
                                  cfg.vocab_size)
        got = step(caches, toks, pos)
        want = _per_layer_decode(cfg, params, caches, toks, pos)
        assert (jax.tree_util.tree_structure(got)
                == jax.tree_util.tree_structure(want))
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w, np.float32))
        if cfg.family != "ssm":
            s_axis = 2 if cfg.mla else 3
            for new, old in zip(got[1], caches):
                kept = np.delete(np.asarray(new, np.float32), pos, s_axis)
                np.testing.assert_array_equal(
                    kept, np.delete(np.asarray(old, np.float32), pos, s_axis))


def test_profile_stream_assembles_with_labels():
    cfg = ModelConfig(name="p", family="moe", n_layers=3, d_model=32,
                      n_heads=4, n_kv_heads=2, d_head=8, d_ff=64,
                      vocab_size=64, n_experts=4, top_k=2, attn_impl="naive",
                      loss_chunk=4)
    params = init_params(lm_specs(cfg), jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    (_, (_, rows)) = lm_loss(cfg, params, toks, toks)
    s = assemble_stream(cfg, rows)
    d = s.decode()
    assert "block0/expert_fullness" in d
    assert d["block2/expert_fullness"].shape == (4,)
    # fullness never exceeds capacity (the FIFO invariant)
    cap = d["block0/capacity"][0]
    for i in range(3):
        assert (d[f"block{i}/expert_fullness"] <= cap).all()


def test_profiling_off_changes_no_math():
    base = dict(name="q", family="dense", n_layers=2, d_model=32, n_heads=4,
                n_kv_heads=2, d_head=8, d_ff=64, vocab_size=64,
                attn_impl="naive", loss_chunk=4)
    cfg_on = ModelConfig(profile_policy="shortcut", **base)
    cfg_off = ModelConfig(profile_policy="off", **base)
    params = init_params(lm_specs(cfg_on), jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    l_on, _ = lm_loss(cfg_on, params, toks, toks)
    l_off, (_, rows_off) = lm_loss(cfg_off, params, toks, toks)
    assert float(l_on) == pytest.approx(float(l_off), rel=1e-6)
    assert rows_off.shape[-1] == 0


# --------------------------------------------------------------------- #
# parameter init
# --------------------------------------------------------------------- #
def _eager_leaf(spec, key):
    """The per-leaf draw done op by op: float32 normal, scale, cast."""
    import math
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    std = (spec.scale if spec.scale is not None
           else 1.0 / math.sqrt(max(1, spec.fan_in)))
    return (jax.random.normal(key, spec.shape, jnp.float32) * std
            ).astype(spec.dtype)


@pytest.mark.parametrize("arch", ["chatglm3-6b", "qwen2.5-14b"])
def test_init_params_bit_identical_to_eager_draws(arch):
    from repro.configs import get_config
    from repro.models.api import model_specs
    from repro.models.params import is_spec

    specs = model_specs(get_config(arch).reduced())
    key = jax.random.PRNGKey(3)
    got = init_params(specs, key)
    leaves, treedef = jax.tree_util.tree_flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    for spec, k, g in zip(leaves, keys, treedef.flatten_up_to(got)):
        want = _eager_leaf(spec, k)
        assert g.dtype == want.dtype and g.shape == want.shape
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(want, np.float32))


def test_init_params_places_leaves_by_sharding():
    from repro.configs import get_config
    from repro.distributed import default_rules, param_shardings
    from repro.launch.mesh import make_host_mesh
    from repro.models.api import model_specs

    specs = model_specs(get_config("chatglm3-6b").reduced())
    shardings = param_shardings(specs, make_host_mesh(), default_rules())
    placed = init_params(specs, jax.random.PRNGKey(3), shardings)
    plain = init_params(specs, jax.random.PRNGKey(3))
    for p, s, q in zip(jax.tree_util.tree_leaves(placed),
                       jax.tree_util.tree_leaves(shardings),
                       jax.tree_util.tree_leaves(plain)):
        assert p.sharding == s
        np.testing.assert_array_equal(np.asarray(p, np.float32),
                                      np.asarray(q, np.float32))

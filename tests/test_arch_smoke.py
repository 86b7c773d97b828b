"""Per-architecture smoke tests: reduced config, one train step on CPU.

Each assigned arch instantiates a REDUCED config of the same family and runs
one forward + gradient step, asserting output shapes and the absence of
NaNs.  The FULL configs are exercised only via the dry-run (abstract shapes).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import abstract_params, count_params, init_params
from repro.models.api import loss_fn, make_batch, model_specs

# analytic parameter counts of the FULL configs (sanity vs the model card)
EXPECTED_PARAMS_B = {
    "chameleon-34b": (33, 36),
    "chatglm3-6b": (5.5, 7),
    "granite-34b": (32, 37),
    "mistral-large-123b": (118, 126),
    "qwen2.5-14b": (13, 16),
    # assignment mandates 48L x 64e x d_ff=1408 (+2 shared); analytically
    # ~29B total / ~4.8B active.  (Upstream Moonlight-16B-A3B has 27 layers;
    # the assignment's layer count is authoritative here.)
    "moonshot-v1-16b-a3b": (26, 31),
    # published Moonlight-16B-A3B: 27 layers, MLA, dense layer 0, 15.96B
    "moonlight-16b-a3b": (15.5, 16.5),
    "qwen3-moe-235b-a22b": (220, 245),
    "mamba2-780m": (0.68, 0.88),
    "zamba2-1.2b": (1.0, 1.5),
    "whisper-base": (0.06, 0.11),
}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_param_count_in_expected_band(arch):
    cfg = get_config(arch)
    n = cfg.param_count()
    lo, hi = EXPECTED_PARAMS_B[arch]
    assert lo * 1e9 <= n <= hi * 1e9, f"{arch}: {n/1e9:.2f}B params"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_smoke_one_train_step(arch):
    cfg = get_config(arch).reduced()
    specs = model_specs(cfg)
    params = init_params(specs, jax.random.PRNGKey(0))
    batch = make_batch(cfg, batch_size=2, seq_len=16)

    (loss, (ce, rows)), grads = jax.value_and_grad(
        lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)

    assert np.isfinite(float(loss)), f"{arch}: loss not finite"
    assert float(loss) > 0
    # profile rows present under the default shortcut policy
    assert rows.shape[0] == cfg.n_layers
    assert np.isfinite(np.asarray(rows)).all()
    # gradients flow to every parameter
    flat = jax.tree_util.tree_leaves(grads)
    assert all(np.isfinite(np.asarray(g, dtype=np.float32)).all() for g in flat)
    total_g = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32)))) for g in flat)
    assert total_g > 0


@pytest.mark.parametrize("arch", ["chatglm3-6b", "qwen3-moe-235b-a22b",
                                  "moonlight-16b-a3b", "mamba2-780m",
                                  "zamba2-1.2b", "whisper-base"])
def test_reduced_smoke_decode_step(arch):
    from repro.models.api import decode_fn, init_caches
    cfg = get_config(arch).reduced()
    specs = model_specs(cfg)
    params = init_params(specs, jax.random.PRNGKey(0))
    caches = init_caches(cfg, batch=2, max_len=16)
    toks = jnp.zeros((2, 1), jnp.int32)
    logits, caches2, rows = decode_fn(cfg, params, caches, toks, 3)
    assert logits.shape == (2, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, dtype=np.float32)).all()


def test_abstract_params_allocate_nothing():
    cfg = get_config("mistral-large-123b")     # 123B — must not materialize
    specs = model_specs(cfg)
    ab = abstract_params(specs)
    leaves = jax.tree_util.tree_leaves(ab)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    n = count_params(specs)
    assert n > 100e9

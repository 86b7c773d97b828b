"""Compile the main path for a described TPU v5e chip (no chip attached).

The TPU compiler refuses what interpret mode and the CPU backend accept:
Pallas blocks not aligned to the (8, 128) tiling, kernels over their fast
memory, programs larger than the chip's 16 GiB.  These tests compile, for
one chip of a described ``v5e:2x2``, the full-width chatglm3-6b serve step,
the per-leaf parameter init, the 256-lane simulator campaign program and
the four Pallas kernels at main-path widths.  The topology is described
inside a fixture, never at import, so every test worker collects the same
tests and only the one given this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_dispatch import moe_dispatch
from repro.kernels.profiled_matmul import profiled_matmul
from repro.kernels.ssd_scan import ssd_state_passing
from repro.models.api import init_caches, model_specs
from repro.models.params import ParamSpec, _init_leaf, is_spec
from repro.rinn import (FaultPlan, RinnConfig, ZCU102, compile_graph,
                        generate_rinn)
from repro.rinn import batchsim
from repro.train.step import make_serve_step

V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs outside
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's executables cannot be read back from the
        # persistent cache, so keep them out of it
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def test_chatglm3_serve_step_fits_one_chip(one_chip):
    """Full width, all 28 layers, batch 8, 160-position cache."""
    cfg = get_config("chatglm3-6b")
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        model_specs(cfg), is_leaf=is_spec)
    caches = _abstract(jax.eval_shape(lambda: init_caches(cfg, 8, 160)),
                       one_chip)
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, caches, tokens, pos).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 12e9          # the weights are real
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM


def test_moonlight_share_serve_step_fits_one_chip(one_chip):
    """Published widths, all 27 layers, 8 of 64 experts held, batch 128 and
    a 640-position latent cache: the moonlight.decode.inline cell's step."""
    cfg = get_config("moonlight-16b-a3b").with_expert_share(0, 8)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        model_specs(cfg), is_leaf=is_spec)
    caches = _abstract(jax.eval_shape(lambda: init_caches(cfg, 128, 640)),
                       one_chip)
    tokens = jax.ShapeDtypeStruct((128, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
        params, caches, tokens, pos).compile()
    mem = compiled.memory_analysis()
    # 6.73 GB of weights and the 2.55 GB latent cache
    assert mem.argument_size_in_bytes > 9e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM


def test_param_init_never_holds_a_float32_leaf(one_chip):
    """The largest chatglm3-6b leaf, [28, 4096, 13696] bf16, is drawn and
    cast in one fused program: no float32 copy of it is ever live."""
    spec = ParamSpec((28, 4096, 13696), jnp.bfloat16)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(_init_leaf, static_argnums=0,
                       out_shardings=one_chip).lower(spec, key).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**26


def test_simulator_campaign_program_compiles(one_chip):
    """The 256-lane run_sim_batch program of the chip smoke campaign."""
    sim = compile_graph(generate_rinn(RinnConfig(
        n_backbone=9, image_size=8, pattern="long_skip", density=0.4,
        seed=21)), ZCU102)
    plan = FaultPlan.generate(sim, seed=1000)
    bucket = batchsim.machine_bucket(sim, batchsim._stall_slots(plan))
    machine = _abstract(batchsim.pack_machine(sim, bucket), one_chip)
    ops, _, _ = batchsim.pack_faults(sim, bucket, plan, None, False, 200_000)
    lanes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((256,) + x.shape, x.dtype,
                                       sharding=one_chip), ops)
    compiled = batchsim._jit_lanes.lower(machine, lanes).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


KERNELS = {
    # causal flash attention: head dim 128 at 4096 positions
    "flash_attention": (
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        [((1, 8, 4096, 128), jnp.bfloat16)] * 3),
    # moonshot-v1-16b-a3b routing: 4096 tokens x top-6 over 64 experts
    "moe_dispatch": (
        lambda e: moe_dispatch(e, 64, 480),
        [((4096 * 6,), jnp.int32)]),
    # a chatglm3-6b d_model x d_model projection of 2048 tokens
    "profiled_matmul": (
        lambda a, b: profiled_matmul(a, b),
        [((2048, 4096), jnp.bfloat16), ((4096, 4096), jnp.bfloat16)]),
    # mamba2-780m: 48 heads x 64 x 128 state, 32 chunks of 128
    "ssd_state_passing": (
        lambda s, d: ssd_state_passing(s, d),
        [((8, 32, 48, 64, 128), jnp.float32), ((8, 32, 48), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # Mosaic, not a fallback

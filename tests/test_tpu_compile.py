"""Compile the main path for a described TPU v5e chip (no chip attached).

The TPU compiler refuses what interpret mode and the CPU backend accept:
Pallas blocks not aligned to the (8, 128) tiling, kernels over their fast
memory, programs larger than the chip's 16 GiB.  These tests compile, for
one chip of a described ``v5e:2x2``, the full-width chatglm3-6b serve step
and the Moonlight-16B-A3B share's, the per-leaf parameter init, the
256-lane simulator campaign program and the five Pallas kernels at
main-path widths, and read the compiled serve steps' cache traffic.  The
topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the one given this file loads the
TPU compiler.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from bench.metrics import _scopes
from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mla_decode import mla_decode
from repro.kernels.moe_dispatch import moe_dispatch
from repro.kernels.profiled_matmul import profiled_matmul
from repro.kernels.ssd_scan import ssd_state_passing
from repro.models.api import init_caches, model_specs
from repro.models.params import ParamSpec, _init_leaf, is_spec
from repro.models.transformer import LatentCaches
from repro.rinn import (FaultPlan, RinnConfig, ZCU102, compile_graph,
                        generate_rinn)
from repro.rinn import batchsim
from repro.train.step import make_serve_step

V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
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
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


SERVE_STEPS = {
    # full width, all 28 layers, batch 8, 160-position cache
    "chatglm3-6b": (lambda: get_config("chatglm3-6b"), 8, 160),
    # published widths, all 27 layers, 8 of 64 experts held, batch 128 and
    # a 640-position latent cache: the moonlight.decode.inline cell's step
    "moonlight-16b-a3b-share": (
        lambda: get_config("moonlight-16b-a3b").with_expert_share(0, 8),
        128, 640),
    # the same at batch 32 and 2048 + 64 positions, which no block of 128
    # divides: the attention kernel's last block runs past the cache
    "moonlight-16b-a3b-share-2112": (
        lambda: get_config("moonlight-16b-a3b").with_expert_share(0, 8),
        32, 2112),
}
MOONLIGHT_STEPS = [n for n in sorted(SERVE_STEPS) if n.startswith("moon")]


@pytest.fixture(scope="module")
def serve_steps(one_chip):
    """Each serve step compiled once for the module, as ``run_serve``
    compiles it: name -> (compiled, weight leaves, caches, max_len)."""
    memo = {}

    def get(name):
        if name not in memo:
            make_cfg, batch, max_len = SERVE_STEPS[name]
            cfg = make_cfg()
            params = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=one_chip),
                model_specs(cfg), is_leaf=is_spec)
            caches = _abstract(jax.eval_shape(
                lambda: init_caches(cfg, batch, max_len)), one_chip)
            tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32,
                                          sharding=one_chip)
            pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
            compiled = jax.jit(make_serve_step(cfg), donate_argnums=(1,)).lower(
                params, caches, tokens, pos).compile()
            memo[name] = (compiled, len(jax.tree_util.tree_leaves(params)),
                          caches, max_len)
        return memo[name]

    return get


def test_chatglm3_serve_step_fits_one_chip(serve_steps):
    """Full width, all 28 layers, batch 8, 160-position cache."""
    mem = serve_steps("chatglm3-6b")[0].memory_analysis()
    assert mem.argument_size_in_bytes > 12e9          # the weights are real
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM


def test_moonlight_share_serve_step_fits_one_chip(serve_steps):
    """Published widths, all 27 layers, 8 of 64 experts held, batch 128 and
    a 640-position latent cache: the moonlight.decode.inline cell's step."""
    mem = serve_steps("moonlight-16b-a3b-share")[0].memory_analysis()
    # 6.73 GB of weights and the 2.83 GB latent cache (rows padded to 640)
    assert mem.argument_size_in_bytes > 9e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM


_COMPUTATION = re.compile(r'^(?:ENTRY )?%([\w.\-]+) .*\{$')
_INSTRUCTION = re.compile(
    r'^\s*(ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\((.*)$')


def _computations(hlo_text):
    """Compiled HLO text -> {computation: {instruction: (dims, opcode,
    operand names, the rest of the line, is root)}}; tuples left out."""
    out, body = {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            body = out.setdefault(m.group(1), {})
            continue
        m = _INSTRUCTION.match(line)
        if m and body is not None:
            dims = tuple(int(d) for d in m.group(3).split(",") if d)
            rest = m.group(5)
            operands = re.findall(r'%([\w.\-]+)', rest.split(")")[0])
            body[m.group(2)] = (dims, m.group(4), operands, rest,
                                bool(m.group(1)))
    return out


def _update_elements(body, name):
    """Elements of the update a dynamic-update-slice writes."""
    return math.prod(body[body[name][2][1]][0])


@pytest.mark.parametrize("name", sorted(SERVE_STEPS))
def test_serve_step_updates_caches_in_place(serve_steps, name):
    """The donated caches are the step's outputs, written one position a
    layer: no stacked cache is allocated anew, copied or rewritten whole,
    and no layer's slice is copied to another layout.  Latent attention
    reads the stacked caches in its kernel: no instruction, fused or not,
    holds one layer's latent rows at any row width."""
    compiled, n_weights, caches, max_len = serve_steps(name)
    text = compiled.as_text()
    aliased = re.findall(r'\((\d+), \{\}, (?:may|must)-alias\)',
                         text.splitlines()[0])
    leaves = jax.tree_util.tree_leaves(caches)
    assert set(range(n_weights, n_weights + len(leaves))) <= {
        int(i) for i in aliased}
    stacked = {c.shape for c in leaves}
    layer = {math.prod(c.shape[1:]) for c in leaves}
    one_position = max(layer) // max_len
    comps = _computations(text)
    fused = set(re.findall(r'calls=%([\w.\-]+)', text))
    latent_rows = {c.shape[1:-1] for c in leaves
                   if isinstance(caches, LatentCaches)}
    for comp, body in comps.items():
        for inst, (dims, *_) in body.items():
            assert dims[:-1] not in latent_rows, f"{comp}: {inst}"
        if comp in fused:
            continue
        for inst, (dims, op, _, rest, _) in body.items():
            where = f"{comp}: {inst}"
            if op in ("copy", "transpose"):
                assert dims not in stacked, where
                assert math.prod(dims) not in layer, where
            if dims not in stacked:
                continue
            assert "AllocateBuffer" not in rest, where
            if op == "fusion":
                called = comps[re.search(r'calls=%([\w.\-]+)', rest).group(1)]
                root = next(k for k, v in called.items() if v[4])
                assert called[root][1] == "dynamic-update-slice", where
                assert _update_elements(called, root) <= one_position, where
            elif op == "dynamic-update-slice":
                assert _update_elements(body, inst) <= one_position, where


def test_latent_attention_kernel_counts_under_attn(serve_steps):
    """The Moonlight step's latent attention is one Mosaic kernel a layer
    (the dense layer's and the scanned stack's), at 640 positions and at
    2112, and each maps to the ``attn`` scope that ``mla_decode_ms``
    reads."""
    for name in MOONLIGHT_STEPS:
        text = serve_steps(name)[0].as_text()
        scopes = _scopes.instruction_scopes(text)
        calls = [_scopes.head(line) for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        assert len(calls) == 2, name
        assert all(scopes[c] == "attn" and "mla_decode" in c
                   for c in calls), name


def test_latent_attention_runs_per_batch_shard(topo):
    """On a 2x2 mesh whose ``data`` axis splits the batch, as the decode
    caches are sharded, each chip runs the kernel on its own half of the
    sequences and of the cache's rows: one kernel and no collective."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.distributed import activation_sharding, default_rules
    from repro.models.mla import _latent_attention

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))

    def attend(q, caches, layer, pos):
        with activation_sharding(mesh, default_rules()):
            return _latent_attention(q, caches, layer, pos, values=512,
                                     scale=192 ** -0.5)

    def arg(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    text = jax.jit(attend).lower(
        arg((128, 16, 576), jnp.bfloat16, "data"),
        arg((26, 128, 640, 640), jnp.bfloat16, None, "data"),
        arg((), jnp.int32), arg((), jnp.int32)).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and "bf16[26,64,640,640]" in calls[0]
    assert not re.search(r"all-gather|all-to-all|all-reduce|"
                         r"collective-permute", text)


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
    compiled = batchsim._program(None, (None, 0)).lower(
        machine, lanes).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


KERNELS = {
    # causal flash attention: head dim 128 at 4096 positions
    "flash_attention": (
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        [((1, 8, 4096, 128), jnp.bfloat16)] * 3),
    # moonlight-16b-a3b latent attention: 16 heads over layer 3 of the
    # 26-layer stack of 640-wide rows at batch 128 and 640 positions
    "mla_decode": (
        lambda q, c, layer, pos: mla_decode(q, c, layer, pos, values=512,
                                            scale=192 ** -0.5),
        [((128, 16, 576), jnp.bfloat16), ((26, 128, 640, 640), jnp.bfloat16),
         ((), jnp.int32), ((), jnp.int32)]),
    # the same at batch 32 and 2048 + 64 positions: 17 blocks of 128, the
    # last one running past the cache
    "mla_decode_2112": (
        lambda q, c, layer, pos: mla_decode(q, c, layer, pos, values=512,
                                            scale=192 ** -0.5),
        [((32, 16, 576), jnp.bfloat16), ((26, 32, 2112, 640), jnp.bfloat16),
         ((), jnp.int32), ((), jnp.int32)]),
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

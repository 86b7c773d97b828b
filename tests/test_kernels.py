"""Per-kernel validation: interpret-mode Pallas vs pure-jnp oracles.

Shape/dtype sweeps + hypothesis property tests, per the assignment: every
kernel asserts allclose against its ref.py oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mla_decode import POSITION_BLOCK, cache_block, mla_decode
from repro.kernels.moe_dispatch import moe_dispatch
from repro.kernels.profiled_matmul import profiled_matmul
from repro.kernels.ssd_scan import ssd_state_passing

I = dict(interpret=True)


def rnd(key, *shape, dtype=jnp.float32, scale=0.5):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype) * scale


# --------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("b,h,t,d,qb,kb", [
    (1, 2, 128, 64, 64, 64),
    (2, 4, 256, 32, 128, 128),
    (1, 1, 64, 128, 32, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_reference(b, h, t, d, qb, kb, dtype):
    q, k, v = (rnd(i, b, h, t, d, dtype=dtype) for i in range(3))
    out, prof = flash_attention(q, k, v, causal=True, q_block=qb,
                                kv_block=kb, **I)
    want, _ = ref.mha_reference(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    assert prof.shape == (b, h, t // qb)


def test_flash_attention_noncausal():
    q, k, v = (rnd(i, 1, 2, 64, 32) for i in range(3))
    out, _ = flash_attention(q, k, v, causal=False, q_block=32, kv_block=32, **I)
    want, _ = ref.mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_profile_stream_matches_oracle():
    """The in-band per-block logit-max records equal the oracle's."""
    q, k, v = (rnd(i + 10, 2, 2, 128, 32) for i in range(3))
    _, prof = flash_attention(q, k, v, causal=True, q_block=32, kv_block=32, **I)
    want = ref.block_logit_max_reference(q, k, causal=True, q_block=32)
    # kernel logits are scaled by 1/sqrt(d) inside; oracle too
    np.testing.assert_allclose(np.asarray(prof), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@settings(deadline=None, max_examples=10)
@given(st.sampled_from([64, 128]), st.sampled_from([16, 32, 64]),
       st.integers(0, 1000))
def test_property_flash_attention_shapes(t, d, seed):
    q, k, v = (rnd(seed + i, 1, 2, t, d) for i in range(3))
    out, _ = flash_attention(q, k, v, causal=True, q_block=t // 2,
                             kv_block=t // 2, **I)
    want, _ = ref.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=5e-5, atol=5e-5)


# --------------------------------------------------------------------- #
# latent-attention decode
# --------------------------------------------------------------------- #
GARBAGE = 1e30      # large and finite: what the kernel must never weigh


@pytest.mark.parametrize("stack,layer", [(1, 0), (3, 2)])
@pytest.mark.parametrize("s", [384, 200])
@pytest.mark.parametrize("attended", [1, 128, 129, "all"])
def test_mla_decode_matches_reference(stack, layer, s, attended):
    """One layer of a stacked latent cache (a one-layer ``prefix`` stack,
    or layer 2 of a ``blocks`` stack), rows of 40 values padded to 128, in
    blocks of 128 positions over 384, or over 200 where the last block
    runs past the cache: the positions past ``pos`` and the padding lanes
    hold garbage that must not reach the output."""
    b, h, c, values, row = 4, 4, 40, 32, 128
    attended = s if attended == "all" else attended
    pos = attended - 1
    caches = rnd(0, stack, b, s, row)
    caches = caches.at[..., c:].set(GARBAGE).at[:, :, pos + 1:].set(GARBAGE)
    q = rnd(1, b, h, c)
    out, row_max = mla_decode(q, caches, layer, pos, values=values,
                              scale=0.125, **I)
    want, want_max = ref.mla_decode_reference(q, caches, layer, pos,
                                              values=values, scale=0.125)
    assert out.shape == (b, h, values) and row_max.shape == (b, h)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(row_max), np.asarray(want_max),
                               rtol=1e-6, atol=1e-6)
    # the pipeline fetches a block when its index changes: count the
    # changes of the cache's index map over the position axis
    blk = POSITION_BLOCK
    scalars = np.array([layer, pos], np.int32)
    index = [tuple(int(v) for v in cache_block(0, j, scalars, blk=blk))
             for j in range(-(-s // blk))]
    fetched = 1 + sum(x != y for x, y in zip(index, index[1:]))
    assert fetched == -(-attended // blk)
    assert {i[0] for i in index} == {layer}


def test_mla_decode_below_one_block_matches_reference():
    """A cache shorter than a block is one block of its own length."""
    caches = rnd(4, 2, 3, 72, 128).at[:, :, 50:].set(GARBAGE)
    q = rnd(5, 3, 2, 64)
    out, row_max = mla_decode(q, caches, 1, 49, values=64, scale=0.2, **I)
    want, want_max = ref.mla_decode_reference(q, caches, 1, 49, values=64,
                                              scale=0.2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(row_max), np.asarray(want_max),
                               rtol=1e-6, atol=1e-6)


def test_mla_decode_bfloat16_matches_reference():
    """The served precision: bfloat16 rows and query, float32 softmax."""
    caches = rnd(2, 2, 8, 256, 128, dtype=jnp.bfloat16)
    q = rnd(3, 8, 4, 72, dtype=jnp.bfloat16)
    out, row_max = mla_decode(q, caches, 1, 200, values=64, scale=0.1, **I)
    want, want_max = ref.mla_decode_reference(q, caches, 1, 200, values=64,
                                              scale=0.1)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(row_max), np.asarray(want_max),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------- #
# moe dispatch
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("m,e,cap,eb,tb", [
    (512, 8, 80, 4, 128),
    (1024, 16, 72, 8, 256),
    (256, 4, 32, 2, 64),
])
def test_moe_dispatch_matches_reference(m, e, cap, eb, tb):
    eids = jax.random.randint(jax.random.PRNGKey(0), (m,), 0, e, jnp.int32)
    slots, counts, fullness, overflow = moe_dispatch(
        eids, e, cap, expert_block=eb, tok_block=tb, **I)
    rs, rc, rf, ro = ref.moe_dispatch_reference(eids, e, cap)
    np.testing.assert_array_equal(np.asarray(slots), np.asarray(rs))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(fullness), np.asarray(rf))
    np.testing.assert_array_equal(np.asarray(overflow), np.asarray(ro))


def test_moe_dispatch_fullness_is_fifo_metric():
    """Skewed routing: buffer saturates at capacity and overflow is exact."""
    eids = jnp.zeros((256,), jnp.int32)  # everything to expert 0
    _, counts, fullness, overflow = moe_dispatch(eids, 4, 100, expert_block=4,
                                                 tok_block=64, **I)
    assert int(counts[0]) == 256
    assert float(fullness[0]) == 100.0
    assert float(overflow[0]) == 156.0


@settings(deadline=None, max_examples=15)
@given(st.integers(0, 10_000), st.sampled_from([4, 8, 16]))
def test_property_moe_dispatch_conservation(seed, e):
    m = 256
    eids = jax.random.randint(jax.random.PRNGKey(seed), (m,), 0, e, jnp.int32)
    slots, counts, fullness, overflow = moe_dispatch(
        eids, e, 32, expert_block=min(e, 8), tok_block=64, **I)
    # total assignments conserved
    assert int(jnp.sum(counts)) == m
    assert float(jnp.sum(fullness + overflow)) == m
    # slots within an expert are unique and dense [0, count)
    s_np, e_np = np.asarray(slots), np.asarray(eids)
    for ex in range(e):
        mine = np.sort(s_np[e_np == ex])
        np.testing.assert_array_equal(mine, np.arange(len(mine)))


# --------------------------------------------------------------------- #
# ssd state passing
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("b,nc,h,p,n,hb", [
    (1, 4, 8, 16, 8, 4),
    (2, 8, 4, 8, 16, 4),
    (1, 2, 16, 32, 4, 8),
])
def test_ssd_state_passing_matches_reference(b, nc, h, p, n, hb):
    states = rnd(0, b, nc, h, p, n)
    decays = jax.nn.sigmoid(rnd(1, b, nc, h))
    out = ssd_state_passing(states, decays, head_block=hb, **I)
    want = ref.ssd_state_passing_reference(states, decays)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ssd_state_passing_composes_with_model_ssd():
    """Kernel output plugs into the chunked SSD exactly like the lax.scan."""
    from repro.models.ssm import ssd_reference
    b, t, h, p, n, chunk = 1, 32, 4, 8, 4, 8
    x = rnd(2, b, t, h, p)
    dt = jax.nn.softplus(rnd(3, b, t, h))
    A = -jnp.exp(rnd(4, h) * 0.5)
    Bm, Cm = rnd(5, b, t, n), rnd(6, b, t, n)
    y_ref, _ = ssd_reference(x, dt, A, Bm, Cm)

    # recompute the chunk states exactly as models/ssm.py does…
    nc = t // chunk
    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    Bc = Bm.reshape(b, nc, chunk, n)
    a = dtc * A[None, None, None, :]
    cum = jnp.cumsum(a, axis=2)
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)
    S = jnp.einsum("bcqh,bcqn,bcqhp->bchpn", decay_to_end * dtc, Bc, xc)
    chunk_decay = jnp.exp(cum[:, :, -1, :])
    # …then let the Pallas kernel do the inter-chunk pass
    states_before = ssd_state_passing(S, chunk_decay, head_block=h, **I)
    want = ref.ssd_state_passing_reference(S, chunk_decay)
    np.testing.assert_allclose(np.asarray(states_before), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------- #
# profiled matmul
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 128, 128, 64, 64, 64),
    (256, 512, 128, 128, 128, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_profiled_matmul_matches_reference(m, k, n, bm, bn, bk, dtype):
    a = rnd(0, m, k, dtype=dtype)
    b = rnd(1, k, n, dtype=dtype)
    out, prof = profiled_matmul(a, b, block_m=bm, block_n=bn, block_k=bk,
                                **I)
    want, want32 = ref.matmul_reference(a, b)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    # the in-band tile absmax records
    want_prof = ref.tile_absmax_reference(a, b, bm, bn)
    np.testing.assert_allclose(np.asarray(prof), np.asarray(want_prof),
                               rtol=tol, atol=tol)


def test_profiled_matmul_profile_off():
    a, b = rnd(0, 64, 64), rnd(1, 64, 64)
    out, prof = profiled_matmul(a, b, block_m=32, block_n=32, block_k=32,
                                profile=False, **I)
    assert prof is None
    want, _ = ref.matmul_reference(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

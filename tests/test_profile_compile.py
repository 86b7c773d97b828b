"""The profiling host path as compiled programs: the serve loop's stream
build (``launch.serve._profile_step``) and the verified decoder's guard
recompute (``core.stream._guard_words``).

Both must give what eager dispatch of the same ops gives, bit for bit, and
compile once for every step of a run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ProfileStream, metrics as M
from repro.core.stream import _guard_words
from repro.launch.serve import _profile_step, run_serve

POLICIES = ("inline", "shortcut")
# (pos, max_len): a tiny cache, the benchmark's warm-up shape, and the
# decode cell's first, middle and last generated steps (prompt 128 of 640)
STEPS = ((5, 8), (0, 5), (127, 640), (383, 640), (638, 640))


def eager_stream(policy, pos, max_len):
    """The stream built op by op, with no compiled program around it."""
    with jax.disable_jit():
        occ = M.kv_occupancy(jnp.full((1,), pos + 1), max_len)
        position = jnp.full((1,), float(pos + 1))
        s = ProfileStream.create()
        if policy == "inline":
            s = s.append_guarded("kv/occupancy", "fifo_fullness", occ)
            return s.append_guarded("kv/position", "position", position)
        row = jnp.concatenate([jnp.atleast_1d(occ), position])
        return s.append_guarded("kv/record", "record_row", row)


def bits(stream):
    return np.asarray(stream.data).view(np.uint32)


@pytest.mark.parametrize("pos,max_len", STEPS)
@pytest.mark.parametrize("policy", POLICIES)
def test_profile_step_matches_eager_build(policy, pos, max_len):
    got, want = _profile_step(policy, pos, max_len), eager_stream(
        policy, pos, max_len)
    assert got.schema == want.schema
    assert got.data.dtype == want.data.dtype == jnp.float32
    np.testing.assert_array_equal(bits(got), bits(want))
    decoded, report = got.decode_verified()
    assert report.ok, report.summary()
    if policy == "inline":
        np.testing.assert_array_equal(decoded["kv/occupancy"],
                                      [pos + 1, max_len])
        np.testing.assert_array_equal(decoded["kv/position"], [pos + 1])
    else:
        np.testing.assert_array_equal(decoded["kv/record"],
                                      [pos + 1, max_len, pos + 1])


def test_profile_step_compiles_once_per_policy():
    _profile_step.clear_cache()
    for n, policy in enumerate(POLICIES, start=1):
        for pos, max_len in STEPS:
            _profile_step(policy, pos, max_len)
            assert _profile_step._cache_size() == n, (policy, pos, max_len)


def guarded(algo, a, b):
    return (ProfileStream.create()
            .append_guarded("a", "m", jnp.asarray(a, jnp.float32), algo=algo)
            .append_guarded("b", "m", jnp.asarray(b, jnp.float32), algo=algo))


@pytest.mark.parametrize("algo", ("xor24", "crc32"))
def test_second_verify_of_same_record_sizes_compiles_nothing(algo):
    _, report = guarded(algo, [1.0, 2.0, 3.0], [4.0]).decode_verified()
    assert report.ok
    other = guarded(algo, [-5.5, 6.25, 7e3], [8.0])
    cached = _guard_words._cache_size()
    decoded, report = other.decode_verified()
    assert report.ok
    assert _guard_words._cache_size() == cached
    np.testing.assert_array_equal(decoded["a"], [-5.5, 6.25, 7e3])


@pytest.mark.parametrize("where", ("payload", "guard"))
@pytest.mark.parametrize("algo", ("xor24", "crc32"))
def test_bitflip_still_quarantined(algo, where):
    s = guarded(algo, [1.0, 2.0, 3.0], [4.0])
    # "b" starts after a's 3 payload words and its guard (seq + checksum)
    b_payload = 3 + s.schema[1].size
    word = b_payload if where == "payload" else b_payload + 2
    decoded, report = s.with_bitflip(word).decode_verified()
    assert report.quarantined == ["b"]
    assert "b" not in decoded
    np.testing.assert_array_equal(decoded["a"], [1.0, 2.0, 3.0])


def test_serve_corruption_steps_inline_down_to_shortcut():
    res = run_serve("chatglm3-6b", reduced=True, batch=1, prompt_len=2,
                    gen=3, corrupt_every=1, failure_threshold=2)
    assert res.tokens.shape == (1, 5)
    assert [(e.from_policy, e.to_policy) for e in res.supervisor.events] == [
        ("inline", "shortcut")]
    assert res.supervisor.policy == "shortcut"
    assert res.collector.integrity_failures == 3
    assert res.collector.quarantine_counts == {"kv/occupancy": 2,
                                               "kv/record": 1}

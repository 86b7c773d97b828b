"""The program's own observability: host spans written with
``jax.profiler.TraceAnnotation``, the profile stream's read counter, and the
model's ``jax.named_scope`` paths in the compiled decode step.

Spans are read back from traces the CPU profiler writes, with the
benchmark's own loader (``bench.tracing``).
"""
import collections
import re

import jax
import jax.numpy as jnp
import pytest

from bench import tracing
from repro.core import ProfileStream, reset_stream_stats, stream_stats

PROFILE_CHILDREN = ("serve.profile.build", "serve.profile.verify",
                    "serve.profile.fold")


def traced(tmp_path, fn):
    """``fn()`` under the profiler; its result and the trace's host spans
    whose names start with a program prefix, by name."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    trace = tracing.load(tracing.find_xplane(str(tmp_path)))
    spans = collections.defaultdict(list)
    for e in sorted(trace.host, key=lambda e: e.start):
        if e.name.startswith(("serve.", "sim.", "train.")):
            spans[e.name].append(e)
    return out, spans


def inside(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


# --------------------------------------------------------------------- #
# serve loop
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("policy,reads", [("inline", 3), ("shortcut", 2),
                                          ("off", 0)])
def test_serve_spans_and_stream_reads(tmp_path, policy, reads):
    from repro.launch.serve import run_serve

    prompt_len, gen = 3, 4
    run = lambda: run_serve("chatglm3-6b", reduced=True, batch=1,  # noqa: E731
                            prompt_len=prompt_len, gen=gen,
                            profile_policy=policy)
    run()                              # compile outside the trace
    reset_stream_stats()
    _, spans = traced(tmp_path, run)
    assert stream_stats() == {"reads": reads * gen,
                              "streams": gen if reads else 0}
    steps = spans["serve.step"]
    assert len(steps) == prompt_len - 1 + gen
    if policy == "off":
        assert not any(n.startswith("serve.profile") for n in spans)
        return
    profiles = spans["serve.profile"]
    assert len(profiles) == gen
    generated = steps[prompt_len - 1:]
    for step, prof in zip(generated, profiles):
        assert step.end <= prof.start     # opened after the dispatch
        children = [[c for c in spans[name] if inside(c, prof)]
                    for name in PROFILE_CHILDREN]
        assert [len(c) for c in children] == [1, 1, 1]
        build, verify, fold = (c[0] for c in children)
        assert build.end <= verify.start and verify.end <= fold.start
    assert all(not inside(s, p) for s in steps for p in profiles)


def test_stream_reads_per_decode():
    from repro.launch.serve import _profile_step

    reset_stream_stats()
    for policy in ("inline", "shortcut"):
        _, report = _profile_step(policy, 5, 8).decode_verified()
        assert report.ok
    assert stream_stats() == {"reads": 3 + 2, "streams": 2}
    s = ProfileStream.create().append_guarded("a", "m", jnp.ones(3),
                                              algo="crc32")
    s.decode_verified()
    s.decode()
    assert stream_stats() == {"reads": 5 + 2 + 1, "streams": 4}
    reset_stream_stats()
    assert stream_stats() == {"reads": 0, "streams": 0}


# --------------------------------------------------------------------- #
# simulator
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def sim():
    from repro.rinn import RinnConfig, ZCU102, compile_graph, generate_rinn

    return compile_graph(generate_rinn(RinnConfig(
        family="conv", n_backbone=6, image_size=6, filters=2, kernel=3,
        pattern="long_skip", density=0.3, seed=1)), ZCU102)


def _sim_calls(sim):
    import repro.rinn as rinn

    plans = [rinn.FaultPlan.generate(sim, seed=s, n_stalls=1)
             for s in range(3)]
    return {
        "single": lambda: rinn.run_sim(sim, faults=plans[0]),
        "batch": lambda: rinn.run_sim_batch(sim, plans=plans),
        "batch_of_one": lambda: rinn.run_sim_batch(sim, plans=plans[:1]),
        "traced": lambda: rinn.run_sim_traced(sim, max_cycles=4000),
        "traced_batch": lambda: rinn.run_sim_traced_batch(
            sim, plans=plans, max_cycles=4000),
        "many": lambda: rinn.run_sim_many([sim, sim, sim], plans=plans),
    }


@pytest.mark.parametrize("entry", ["single", "batch", "batch_of_one",
                                   "traced", "traced_batch", "many"])
def test_sim_spans_one_pair_per_launch(tmp_path, sim, entry):
    from repro.rinn import compile_stats

    call = _sim_calls(sim)[entry]
    call()                             # compile outside the trace
    before = compile_stats()["launches"]
    results, spans = traced(tmp_path, call)
    launches = compile_stats()["launches"] - before
    assert launches >= 1 and results
    assert len(spans["sim.pack"]) == len(spans["sim.unpack"]) == launches
    for pack, unpack in zip(spans["sim.pack"], spans["sim.unpack"]):
        assert pack.end <= unpack.start


# --------------------------------------------------------------------- #
# model scopes
# --------------------------------------------------------------------- #
def test_decode_step_hlo_carries_model_scopes():
    from repro.configs import get_config
    from repro.models import init_params
    from repro.models.api import init_caches, model_specs
    from repro.train.step import make_serve_step

    cfg = get_config("chatglm3-6b").reduced()
    specs = model_specs(cfg)
    params = jax.eval_shape(lambda: init_params(specs, jax.random.PRNGKey(0)))
    caches = jax.eval_shape(lambda: init_caches(cfg, 2, 8))
    tokens = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    lowered = jax.jit(make_serve_step(cfg)).lower(params, caches, tokens, 0)
    text = lowered.as_text(debug_info=True)
    for path in ("attn", "attn/kv_update", "mlp", "norm", "embed", "logits"):
        assert re.search(rf'loc\("([^"]*/)?{path}/', text), path
    # the compiled program keeps them in its instructions' op_name metadata
    from bench.metrics import _scopes

    scopes = _scopes.instruction_scopes(lowered.compile().as_text())
    assert {"attn", "mlp", "norm", "embed", "logits"} <= set(scopes.values())


# --------------------------------------------------------------------- #
# training loop
# --------------------------------------------------------------------- #
def test_train_spans_and_step_times(tmp_path):
    from repro.configs import get_config
    from repro.launch.train import run_train

    steps = 3
    res, spans = traced(tmp_path / "trace", lambda: run_train(
        get_config("chatglm3-6b").reduced(), steps=steps, batch=2, seq=16))
    assert len(res.step_end_s) == len(res.losses) == steps
    assert all(a < b for a, b in zip(res.step_end_s, res.step_end_s[1:]))
    assert len(spans["train.step"]) == len(spans["train.profile"]) == steps
    for step, prof in zip(spans["train.step"], spans["train.profile"]):
        assert step.end <= prof.start

"""The readers of the program's own spans, counter and scopes, on synthetic
traces with known answers; each gives None on a program without them."""
import types

import pytest

from bench import run
from bench.tracing import DeviceLines, Event, Trace


def ev(name, a, b):
    return Event(name, float(a), float(b))


def reader(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py").read


def serve_ctx(trace, window=(20, 60)):
    records = {"program": "serve_step", "batch": 2, "prompt_len": 4,
               "gen": 3, "config": {"program": {"arch": "chatglm3-6b",
                                                "reduced": True}}}
    return types.SimpleNamespace(trace=trace, records=records,
                                 driver_window=lambda t: window)


def serve_trace(with_spans=True):
    # three generated steps: the serve step runs on the device at [0, 10),
    # [20, 30) and [42, 52); the profiling path's programs after each.  The
    # decode window (20, 60) times the last two.
    mods = [ev("jit_serve_step(1)", 0, 10), ev("jit_conc(2)", 12, 13),
            ev("jit_serve_step(1)", 20, 30), ev("jit_conc(2)", 32, 33),
            ev("jit_xor(3)", 34, 35), ev("jit_xor(3)", 36, 37),
            ev("jit_serve_step(1)", 42, 52), ev("jit_conc(2)", 54, 55)]
    ops = [ev("%while.9 = (bf16[8]) while(bf16[8] %t)", 0, 10),
           ev("%fusion.1 = bf16[8] fusion(...)", 1, 4),
           ev("%fusion.2 = bf16[8] fusion(...)", 5, 9),
           ev("%while.9 = (bf16[8]) while(bf16[8] %t)", 20, 30),
           ev("%fusion.1 = bf16[8] fusion(...)", 21, 24),
           ev("%fusion.2 = bf16[8] fusion(...)", 25, 29),
           ev("%concatenate.1 = f32[4] concatenate(...)", 32, 33),
           ev("%while.9 = (bf16[8]) while(bf16[8] %t)", 42, 52),
           ev("%fusion.1 = bf16[8] fusion(...)", 43, 46),
           ev("%fusion.2 = bf16[8] fusion(...)", 47, 50),
           ev("%copy.3 = bf16[8] copy(...)", 50, 51)]
    host = [ev("bench.serve.call", 0, 70)]
    if with_spans:
        for a, b in ((1, 19), (21, 40), (41, 60)):
            host += [ev("serve.step", a - 1, a), ev("serve.profile", a, b),
                     ev("serve.profile.build", a, a + 2),
                     ev("serve.profile.verify", a + 2, b - 2),
                     ev("serve.profile.fold", b - 2, b - 1)]
    return Trace({"/device:TPU:0": DeviceLines(ops, mods)}, host)


def test_profile_host_ms_is_the_mean_span_of_the_timed_steps():
    # spans (21, 40) and (41, 60) overlap the window; (1, 19) does not
    assert reader("profile_host_ms")(serve_ctx(serve_trace()), None) == \
        pytest.approx(1e-6 * (19 + 19) / 2)


def test_profile_programs_per_step_leaves_out_the_serve_step():
    # inside (21, 40): conc, xor, xor; inside (41, 60): the serve step
    # (left out) and conc
    ctx = serve_ctx(serve_trace())
    assert reader("profile_programs_per_step")(ctx, ctx.records) == 2.0


def test_profile_readers_without_spans_read_nothing():
    ctx = serve_ctx(serve_trace(with_spans=False))
    assert reader("profile_host_ms")(ctx, ctx.records) is None
    assert reader("profile_programs_per_step")(ctx, ctx.records) is None


def test_profile_reads_per_step_reads_the_program_counter(monkeypatch):
    import repro.core
    from repro.core import ProfileStream, reset_stream_stats

    read = reader("profile_reads_per_step")
    reset_stream_stats()
    assert read(None, None) is None
    s = (ProfileStream.create().append_guarded("a", "m", [1.0])
         .append_guarded("b", "m", [2.0]))
    s.decode_verified()
    s.decode_verified()
    assert read(None, None) == 3.0
    monkeypatch.delattr(repro.core, "stream_stats")
    assert read(None, None) is None


HLO = """
%fused_computation.1 (p: bf16[8]) -> bf16[8] {
  %add.7 = bf16[8] add(%p, %p), metadata={op_name="jit(serve_step)/while/body/closed_call/attn/kv_update/add"}
}
ENTRY %main {
  %fusion.1 = bf16[8] fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(serve_step)/while/body/closed_call/attn/dot_general" stack_frame_id=3}
  %fusion.2 = bf16[8] fusion(%x), kind=kOutput, metadata={op_name="jit(serve_step)/while/body/closed_call/mlp/dot_general"}
  %copy.3 = bf16[8] copy(%x), metadata={op_name="jit(serve_step)/while"}
  %copy-start.5 = (bf16[8], bf16[8], u32[]) copy-start(%x)
  ROOT %while.9 = (bf16[8]) while(%t), condition=%c, body=%b, metadata={op_name="jit(serve_step)/while"}
}
"""


def test_instruction_scopes_take_the_first_model_scope():
    from bench.metrics import _scopes

    assert _scopes.instruction_scopes(HLO) == {
        "%add.7 = bf16[8] add": "attn", "%fusion.1 = bf16[8] fusion": "attn",
        "%fusion.2 = bf16[8] fusion": "mlp",
        "%copy.3 = bf16[8] copy": "unscoped",
        "%copy-start.5 = (bf16[8], bf16[8], u32[]) copy-start": "unscoped",
        "%while.9 = (bf16[8]) while": "unscoped"}
    assert _scopes.head("%fusion.12 = (u32[1]{0:T(128)}, u32[1]{0:T(128)}) "
                        "fusion(u32[2]{0:T(128)} %key.1), kind=kLoop") == \
        "%fusion.12 = (u32[1]{0:T(128)}, u32[1]{0:T(128)}) fusion"
    assert _scopes.scope_of("jit(f)/norm/attn/x") == "norm"
    assert _scopes.scope_of("jit(f)/while/body/norms/x") == "unscoped"


@pytest.mark.parametrize("scope,ms", [("attn", 3), ("mlp", 3.5),
                                      ("unscoped", 3.5)])
def test_decode_scope_ms_per_timed_step(monkeypatch, scope, ms):
    from bench.metrics import _scopes

    monkeypatch.setattr(_scopes, "compiled_step_text", lambda records: HLO)
    ctx = serve_ctx(serve_trace())
    # timed steps [20, 30) and [42, 52): attn 3 + 3, mlp 4 + 3, unscoped
    # (the loop's own time 3 + 3, the copy 1); the concatenate at [32, 33)
    # is no part of the step
    assert reader(f"decode_{scope}_ms")(ctx, ctx.records) == \
        pytest.approx(1e-6 * ms)


@pytest.mark.parametrize("hlo", [
    HLO.replace("attn", "x").replace("mlp", "y"),
    HLO.replace("%copy.3", "%copy.4"),
    HLO.replace("%copy.3 = bf16[8]", "%copy.3 = f32[8]")])
def test_decode_scope_ms_reads_nothing_it_cannot_attribute(monkeypatch, hlo):
    # a program without model scopes, or a traced operation the compiled
    # program does not hold under that name, result type and opcode
    from bench.metrics import _scopes

    monkeypatch.setattr(_scopes, "compiled_step_text", lambda records: hlo)
    ctx = serve_ctx(serve_trace())
    assert reader("decode_attn_ms")(ctx, ctx.records) is None


def sim_ctx(with_spans=True):
    host = [ev("bench.campaign.call", 0, 100),
            ev("bench.campaign.call", 200, 300)]
    if with_spans:
        host += [ev("sim.pack", 1, 31), ev("sim.unpack", 60, 90),
                 ev("sim.pack", 201, 221), ev("sim.unpack", 260, 270),
                 ev("sim.pack", 400, 500)]       # outside every call
    driver = types.SimpleNamespace(span_name="bench.campaign.call")
    return types.SimpleNamespace(trace=Trace({}, host), driver=driver)


@pytest.mark.parametrize("name,ns", [("sim_pack_ms", 25), ("sim_unpack_ms",
                                                            20)])
def test_sim_span_ms_per_call(name, ns):
    assert reader(name)(sim_ctx(), None) == pytest.approx(1e-6 * ns)
    assert reader(name)(sim_ctx(with_spans=False), None) is None

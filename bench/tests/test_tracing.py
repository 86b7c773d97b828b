"""The trace reduction on synthetic traces with known intervals, and the
loader on a trace the CPU profiler writes."""
import pytest

from bench import tracing
from bench.tracing import DeviceLines, Event, Trace


def ev(name, a, b):
    return Event(name, float(a), float(b))


def synthetic():
    # device ops: busy [10, 30) and [40, 50) and [45, 60) -> union 40 ns
    ops = [ev("fusion.1", 10, 30), ev("fusion.2", 40, 50),
           ev("copy.3", 45, 60)]
    mods = [ev("jit_step(1)", 10, 30), ev("jit_step(1)", 40, 60),
            ev("jit_other(2)", 70, 71)]
    host = [ev("bench.call", 0, 100), ev("host_work", 30, 40),
            ev("inner", 33, 38)]
    return Trace({"/device:TPU:0": DeviceLines(ops, mods)}, host)


def test_union_and_gaps():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert tracing.covered([(0, 3), (5, 9)], 1, 6) == 3
    assert tracing.gaps([(0, 3), (5, 9)], -1, 10) == [(-1, 0), (3, 5),
                                                       (9, 10)]
    assert tracing.union([(4, 4), (3, 2)]) == []


def test_busy_idle_and_window():
    t = synthetic()
    assert tracing.busy_ns(t, 0, 100) == 40
    assert tracing.idle_share(t, 0, 100) == pytest.approx(0.6)
    assert tracing.idle_share(t, 10, 30) == pytest.approx(0.0)
    assert tracing.idle_share(t, 80, 90) is None
    assert tracing.span_window(t) == (0, 100)
    assert tracing.span_window(t, "nothing.") is None


def test_busy_averages_over_devices():
    t = synthetic()
    t.devices["/device:TPU:1"] = DeviceLines([ev("f", 0, 100)], [])
    assert tracing.busy_ns(t, 0, 100) == pytest.approx(70)


def test_self_times_of_nested_ops():
    # a while op [0, 100) holding body ops [10, 30) and [40, 50), the
    # second holding [42, 44); long HLO text shortened to its name
    ops = [ev("%while.1 = (s32[]) while(...)", 0, 100),
           ev("%fusion.2 = f32[8] fusion(...)", 10, 30),
           ev("%fusion.3 = f32[8] fusion(...)", 40, 50),
           ev("%copy.4 = f32[8] copy(...)", 42, 44)]
    own = tracing.self_times(ops, 0, 100)
    assert own == {"%while.1": 70, "%fusion.2": 20, "%fusion.3": 8,
                   "%copy.4": 2}
    assert tracing.self_times(ops, 20, 45) == {
        "%while.1": 10, "%fusion.2": 10, "%fusion.3": 3, "%copy.4": 2}


def test_modules_and_top_ops():
    t = synthetic()
    assert [e.duration for e in t.modules("jit_step")] == [20, 20]
    top = tracing.top_ops(t, 0, 100)
    assert top[0] == ("fusion.1", pytest.approx(20e-9))
    assert [n for n, _ in top] == ["fusion.1", "copy.3", "fusion.2"]
    # clipped to the window
    assert tracing.top_ops(t, 20, 42)[0] == ("fusion.1", pytest.approx(10e-9))


def test_idle_gaps_labelled_by_innermost_host_event():
    t = synthetic()
    gaps = dict(tracing.idle_gaps(t, 0, 100))
    # [30, 40) has midpoint 35 inside "inner"; [0, 10) and [60, 100)
    # lie only inside the benchmark's span
    assert gaps["inner"] == pytest.approx(10e-9)
    assert gaps["bench.call"] == pytest.approx(50e-9)
    assert tracing.labels_at(t.host, [200, 35, 5]) == ["none", "inner",
                                                        "bench.call"]


def test_load_reads_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((64,))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.test.call"):
            f(x).block_until_ready()
    trace = tracing.load(tracing.find_xplane(str(tmp_path)))
    spans = trace.spans("bench.test.")
    assert len(spans) == 1 and spans[0].duration > 0
    with pytest.raises(FileNotFoundError):
        tracing.find_xplane(str(tmp_path / "empty"))

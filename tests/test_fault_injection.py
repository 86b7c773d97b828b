"""Fault-injection and graceful-degradation tests (the robustness layer).

Covers all four layers: FaultPlan injection in the stream simulator,
DeadlockReport + auto-remediation in cosim, profile-stream integrity
(checksum guards, quarantine), and the serve/train supervision ladder.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ProfileCollector, ProfileStream
from repro.core.codec import word_checksum
from repro.distributed.fault import (
    ProfilingSupervisor, RetryPolicy, Watchdog, retry_with_backoff,
)
from repro.rinn import (
    BeatFault, CapacityFault, DeadlockError, FaultPlan, NodeStall,
    RinnConfig, WordCorruption, ZCU102, compile_graph, cosim_only,
    diagnose, generate_rinn, run_sim, run_with_remediation,
)


def skip_graph(seed=1):
    return generate_rinn(RinnConfig(
        family="conv", n_backbone=6, image_size=6, filters=2, kernel=3,
        pattern="long_skip", density=0.3, seed=seed))


@pytest.fixture(scope="module")
def sim():
    return compile_graph(skip_graph(), ZCU102)


# --------------------------------------------------------------------- #
# layer 1: FaultPlan injection in the stream simulator
# --------------------------------------------------------------------- #
def test_fault_plan_generation_is_seed_deterministic(sim):
    p1 = FaultPlan.generate(sim, seed=11, n_stalls=2, n_drops=1,
                            n_corruptions=1, horizon=100)
    p2 = FaultPlan.generate(sim, seed=11, n_stalls=2, n_drops=1,
                            n_corruptions=1, horizon=100)
    assert p1 == p2
    p3 = FaultPlan.generate(sim, seed=12, n_stalls=2, n_drops=1,
                            n_corruptions=1, horizon=100)
    assert p1 != p3


def test_injected_fault_runs_are_deterministic(sim):
    plan = FaultPlan.generate(sim, seed=5, n_stalls=2, n_corruptions=1,
                              horizon=100)
    r1 = run_sim(sim, profiled=True, faults=plan)
    r2 = run_sim(sim, profiled=True, faults=plan)
    assert r1.cycles == r2.cycles
    assert r1.fifo_max == r2.fifo_max
    assert r1.fifo_profiled == r2.fifo_profiled


def test_node_stall_delays_completion(sim):
    base = run_sim(sim)
    assert base.completed
    # stall the sink: no pipeline slack can hide it
    sink = sim.node_ids[-1]
    stalled = run_sim(sim, faults=FaultPlan(
        stalls=(NodeStall(node=sink, start=0, duration=base.cycles),)))
    assert stalled.completed
    assert stalled.cycles > base.cycles


def test_dropped_beat_starves_downstream(sim):
    e = sim.edge_list[2]
    res = run_sim(sim, faults=FaultPlan(drops=(BeatFault(edge=e, beat=3),)),
                  max_cycles=50_000)
    assert not res.completed
    assert res.deadlocked
    # detection is prompt: far below the max_cycles burn
    assert res.cycles < 5_000
    # the starved consumer never got its full beat count
    assert res.node_consumed[e[1]] < run_sim(sim).node_consumed[e[1]]


def test_duplicated_beat_leaves_residue(sim):
    e = sim.edge_list[2]
    res = run_sim(sim, faults=FaultPlan(dups=(BeatFault(edge=e, beat=3),)),
                  max_cycles=50_000)
    assert res.completed
    assert res.fifo_final[e] == 1  # the surplus beat never drains


def test_capacity_fault_deadlocks_and_is_diagnosed(sim):
    base = run_sim(sim)
    edge = max(base.fifo_max, key=base.fifo_max.get)
    res = run_sim(sim, faults=FaultPlan(
        capacities=(CapacityFault(edge=edge, capacity=1),)),
        max_cycles=50_000)
    assert not res.completed and res.deadlocked
    report = diagnose(sim, res)
    assert report.capacity_induced
    assert edge in report.full_edges
    assert edge in report.blocked_edge_set


def test_profile_word_bitflip_lands_in_profiled_reading(sim):
    clean = run_sim(sim, profiled=True)
    edge = next(iter(clean.fifo_profiled))
    plan = FaultPlan(corruptions=(
        WordCorruption(edge=edge, cycle=50, bitmask=1 << 20),))
    dirty = run_sim(sim, profiled=True, faults=plan)
    assert dirty.completed  # corruption poisons the reading, not the run
    assert dirty.fifo_profiled[edge] != clean.fifo_profiled[edge]
    assert dirty.fifo_profiled[edge] >= 1 << 20  # implausible: detectable


# --------------------------------------------------------------------- #
# layer 2: deadlock diagnosis + auto-remediation
# --------------------------------------------------------------------- #
def test_deadlock_raises_structured_report_not_bare_runtimeerror():
    g = skip_graph()
    with pytest.raises(DeadlockError) as ei:
        cosim_only(g, ZCU102.with_(fifo_capacity=4), max_cycles=20_000)
    report = ei.value.report
    assert report.blocked, "report must name the blocked cycle of actors"
    assert report.blocked_edge_set, "report must name the blocked edge set"
    assert report.capacity_induced
    # the summary names full FIFOs and a remediation suggestion
    text = report.summary()
    assert "full" in text and "remediation" in text
    # a blocked actor knows what it waits on
    stuck = [a for a in report.blocked if a.full_outputs or a.empty_inputs]
    assert stuck


def test_auto_remediation_resolves_capacity_deadlock():
    g = skip_graph()
    timing = ZCU102.with_(fifo_capacity=4)
    with pytest.raises(DeadlockError):
        cosim_only(g, timing, max_cycles=20_000)
    res = cosim_only(g, timing, max_cycles=20_000, auto_remediate=True)
    assert res.completed


def test_remediation_attempt_log_and_grown_capacities():
    sim4 = compile_graph(skip_graph(), ZCU102.with_(fifo_capacity=4))
    res, attempts = run_with_remediation(sim4)
    assert res.completed
    assert attempts and attempts[-1].completed
    # capacities grew monotonically across attempts
    grown = attempts[-1].overrides
    assert grown and all(c > 4 for c in grown.values())


def test_remediation_gives_up_on_starvation(sim):
    e = sim.edge_list[2]
    res, attempts = run_with_remediation(
        sim, faults=FaultPlan(drops=(BeatFault(edge=e, beat=3),)))
    assert not res.completed
    assert len(attempts) == 1  # one diagnosis, no futile sizing attempts
    assert not attempts[-1].report.capacity_induced


def test_fault_plan_recorded_in_report(sim):
    plan = FaultPlan(seed=9, capacities=(
        CapacityFault(edge=max(run_sim(sim).fifo_max,
                               key=run_sim(sim).fifo_max.get), capacity=1),))
    res = run_sim(sim, faults=plan, max_cycles=50_000)
    report = diagnose(sim, res)
    assert report.faults is plan
    assert "fault plan" in report.summary()


# --------------------------------------------------------------------- #
# layer 3: profile-stream integrity
# --------------------------------------------------------------------- #
def guarded_stream():
    s = ProfileStream.create()
    s = s.append_guarded("l0/rms", "act_rms", jnp.array([1.5, 2.5]))
    s = s.append_guarded("l1/rms", "act_rms", jnp.array([3.0]))
    s = s.append_guarded("l2/mx", "act_max", jnp.array([7.0, 8.0, 9.0]))
    return s


def test_checksum_detects_any_single_bitflip():
    vals = jnp.array([1.5, -2.25, 3e5], jnp.float32)
    base = float(word_checksum(vals))
    for word in range(3):
        for bit in (0, 7, 19, 30):
            bits = np.asarray(vals).view(np.uint32).copy()
            bits[word] ^= np.uint32(1 << bit)
            flipped = jnp.asarray(bits.view(np.float32))
            assert float(word_checksum(flipped)) != base, (word, bit)


def test_clean_guarded_stream_verifies():
    d, rep = guarded_stream().decode_verified()
    assert rep.ok
    assert set(rep.status.values()) == {"ok"}
    np.testing.assert_allclose(d["l0/rms"], [1.5, 2.5])


def test_corrupted_signal_quarantined_others_intact():
    # word 4 is l1/rms's payload (2 payload + 2 guard words precede it)
    bad = guarded_stream().with_bitflip(4)
    d, rep = bad.decode_verified()
    assert not rep.ok
    assert rep.quarantined == ["l1/rms"]
    assert "l1/rms" not in d
    np.testing.assert_allclose(d["l0/rms"], [1.5, 2.5])
    np.testing.assert_allclose(d["l2/mx"], [7.0, 8.0, 9.0])


def test_flipped_guard_word_quarantines_its_record():
    # word 5 is l1's sequence word; word 6 its checksum
    for w in (6,):
        d, rep = guarded_stream().with_bitflip(w).decode_verified()
        assert rep.quarantined == ["l1/rms"], w


def test_nonfinite_sequence_word_never_crashes_decoder():
    # flipping bit 30 of seq word 1.0 yields exactly +inf; the verified
    # decoder must report it, not raise OverflowError on int(inf)
    bad = guarded_stream().with_bitflip(5, bitmask=1 << 30)
    d, rep = bad.decode_verified()
    assert not rep.ok
    assert any("unreadable sequence" in e for e in rep.seq_errors)
    np.testing.assert_allclose(d["l0/rms"], [1.5, 2.5])  # others intact


def test_truncated_stream_partial_decode_instead_of_crash():
    s = guarded_stream()
    cut = s.truncated(6)
    with pytest.raises(ValueError):
        cut.decode()  # the strict decoder refuses
    d, rep = cut.decode_verified()
    assert rep.truncated and not rep.ok
    assert "l2/mx" in rep.missing
    np.testing.assert_allclose(d["l0/rms"], [1.5, 2.5])


def test_unguarded_streams_still_verify_as_unverified():
    s = ProfileStream.create().append("a", "m", jnp.array([1.0]))
    d, rep = s.decode_verified()
    assert rep.ok  # length matches, nothing corrupt — just unverified
    assert rep.status["a"] == "unverified"
    np.testing.assert_allclose(d["a"], [1.0])


def test_split_merge_preserves_guard_verification():
    s = guarded_stream()
    a, b = s.split(2)
    b = b.append_guarded("branch/x", "m", jnp.array([4.0]))
    m = ProfileStream.merge(a, b)
    d, rep = m.decode_verified()
    assert rep.ok, rep.summary()
    assert set(d) == {"l0/rms", "l1/rms", "l2/mx", "branch/x"}


def test_collector_quarantine_accounting():
    c = ProfileCollector()
    c.ingest_verified(guarded_stream())
    c.ingest_verified(guarded_stream().with_bitflip(4))
    assert c.integrity_failures == 1
    assert c.quarantine_counts == {"l1/rms": 1}
    # the intact copy of l1/rms from step 1 still aggregated
    assert "l1/rms" in c.signals
    assert "integrity" in c.report()


# --------------------------------------------------------------------- #
# layer 3b: CRC-32 guard mode + truncated/interleaved verified decode
# --------------------------------------------------------------------- #
def crc_stream():
    s = ProfileStream.create()
    s = s.append_guarded("l0/rms", "act_rms", jnp.array([1.5, 2.5]),
                         algo="crc32")
    s = s.append_guarded("l1/rms", "act_rms", jnp.array([3.0]),
                         algo="crc32")
    return s


def test_crc32_matches_reference_implementation():
    import binascii

    from repro.core.codec import word_crc32

    for vals in ([1.5, -2.25, 3e5], [0.0], list(range(50))):
        v = np.asarray(vals, "<f4")
        lo, hi = np.asarray(word_crc32(jnp.asarray(v)))
        assert int(lo) | (int(hi) << 16) == binascii.crc32(v.tobytes())


def test_crc32_guard_verifies_and_quarantines():
    d, rep = crc_stream().decode_verified()
    assert rep.ok, rep.summary()
    np.testing.assert_allclose(d["l0/rms"], [1.5, 2.5])
    # payload flip -> that record quarantined, the other intact
    d, rep = crc_stream().with_bitflip(0).decode_verified()
    assert rep.quarantined == ["l0/rms"] and "l1/rms" in d
    # flip inside either CRC half -> quarantined too
    for w in (3, 4):  # l0: payload 0-1, guard [seq, lo, hi] = 2-4
        _, rep = crc_stream().with_bitflip(w).decode_verified()
        assert rep.quarantined == ["l0/rms"], w


def test_crc32_detects_multi_bit_burst():
    # a 17-bit burst inside one word — the kind of damage a DMA glitch
    # leaves; CRC-32 must flag it
    bad = crc_stream().with_bitflip(1, bitmask=(1 << 17) - 1)
    _, rep = bad.decode_verified()
    assert rep.quarantined == ["l0/rms"]


def test_default_guard_stays_two_words():
    s = ProfileStream.create().append_guarded("a", "m", jnp.array([1.0]))
    assert s.schema[-1].size == 2  # xor24 layout unchanged by the new mode


def test_truncated_crc_guard_keeps_payload_unverified():
    s = crc_stream()
    # cut mid-guard: l0's payload arrived, only part of its guard did
    d, rep = s.truncated(3).decode_verified()
    assert rep.truncated and not rep.ok
    assert rep.status["l0/rms"] == "unverified"
    np.testing.assert_allclose(d["l0/rms"], [1.5, 2.5])
    assert "l1/rms" in rep.missing


def test_truncation_sweep_never_crashes_verified_decode():
    s = crc_stream()
    for n in range(s.n_words + 1):
        d, rep = s.truncated(n).decode_verified()
        assert rep.ok == (n == s.n_words)
        for name, vals in d.items():
            assert np.isfinite(vals).all(), (n, name)


def test_interleaved_guard_algorithms_decode_positionally():
    # mixed xor24/crc32 records in one stream: the decoder must key the
    # verification off each guard label's size, not a global mode
    s = ProfileStream.create()
    s = s.append_guarded("a", "m", jnp.array([1.0]), algo="crc32")
    s = s.append_guarded("b", "m", jnp.array([2.0]))            # xor24
    s = s.append_guarded("c", "m", jnp.array([3.0]), algo="crc32")
    d, rep = s.decode_verified()
    assert rep.ok, rep.summary()
    assert [s2.size for s2 in s.schema if s2.metric == "integrity"] == [3, 2, 3]
    assert set(d) == {"a", "b", "c"}
    # corruption in the middle xor24 record leaves both crc records intact
    bad, rep = s.with_bitflip(4).decode_verified()
    assert rep.quarantined == ["b"] and set(bad) == {"a", "c"}


def test_interleaved_split_merge_with_crc_guards():
    a, b = crc_stream().split(2)
    b = b.append_guarded("branch/x", "m", jnp.array([4.0]))     # xor24
    d, rep = ProfileStream.merge(a, b).decode_verified()
    assert rep.ok, rep.summary()
    assert set(d) == {"l0/rms", "l1/rms", "branch/x"}


# --------------------------------------------------------------------- #
# layer 4: supervision — watchdog, retry, degradation ladder
# --------------------------------------------------------------------- #
def test_retry_with_backoff_retries_then_succeeds():
    calls = {"n": 0}
    delays = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    out = retry_with_backoff(
        flaky, policy=RetryPolicy(retries=3, base_delay=0.01, backoff=2.0),
        sleep=delays.append)
    assert out == "ok" and calls["n"] == 3
    assert delays == [0.01, 0.02]  # exponential


def test_retry_with_backoff_exhausts_and_raises():
    def always():
        raise RuntimeError("permanent")

    with pytest.raises(RuntimeError):
        retry_with_backoff(always, policy=RetryPolicy(retries=2),
                           sleep=lambda _d: None)


def test_retry_with_backoff_raises_first_error_of_donating_step():
    """A failed step that deleted (donated) its inputs is not retried: the
    device error it raised is the one the caller sees."""
    import jax
    import jax.numpy as jnp

    calls = {"n": 0}

    def donating_step(buf):
        calls["n"] += 1
        if buf.is_deleted():
            raise RuntimeError("Array has been deleted")
        buf.delete()  # what a jitted step with donate_argnums does
        raise jax.errors.JaxRuntimeError("INTERNAL: device fault")

    with pytest.raises(jax.errors.JaxRuntimeError, match="device fault"):
        retry_with_backoff(donating_step, jnp.ones(4),
                           policy=RetryPolicy(retries=3),
                           sleep=lambda _d: None)
    assert calls["n"] == 1


def test_watchdog_counts_consecutive_breaches():
    wd = Watchdog(budget_s=1.0)
    assert not wd.observe(0.5)
    assert wd.observe(2.0) and wd.breaches == 1
    assert wd.observe(3.0) and wd.breaches == 2
    assert not wd.observe(0.1) and wd.breaches == 0
    assert wd.total_breaches == 2


def test_supervisor_ladder_degrades_and_data_path_stays_up():
    sup = ProfilingSupervisor(failure_threshold=2)
    assert sup.policy == "inline"
    sup.record_integrity_failure()
    assert sup.policy == "inline"  # one strike is not enough
    sup.record_integrity_failure()
    assert sup.policy == "shortcut"
    sup.step_ok()  # healthy step resets the streak
    sup.record_integrity_failure()
    assert sup.policy == "shortcut"
    sup.record_integrity_failure()
    sup.record_integrity_failure()
    assert sup.policy == "off" and not sup.active
    # pinned at the bottom rung, never raises
    sup.record_integrity_failure()
    assert sup.policy == "off"
    assert [e.to_policy for e in sup.events] == ["shortcut", "off"]


def test_supervisor_overhead_budget_trigger():
    sup = ProfilingSupervisor(failure_threshold=2, overhead_budget=0.2)
    sup.record_overhead(0.1)
    sup.record_overhead(0.5)
    sup.record_overhead(0.5)
    assert sup.policy == "shortcut"
    assert "overhead" in sup.events[0].reason


def test_serve_degrades_profiling_but_keeps_producing_tokens():
    from repro.launch.serve import run_serve

    res = run_serve("qwen2.5-14b", reduced=True, batch=2, prompt_len=4,
                    gen=6, corrupt_every=1, failure_threshold=2)
    # tokens kept flowing to the very end
    assert res.tokens.shape == (2, 10)
    # the ladder walked all the way down under sustained corruption
    assert res.supervisor.policy == "off"
    assert [e.to_policy for e in res.supervisor.events] == ["shortcut", "off"]
    # every damaged stream was quarantined, not crashed on
    assert res.collector.integrity_failures >= 2


def test_serve_clean_run_never_degrades():
    from repro.launch.serve import run_serve

    res = run_serve("qwen2.5-14b", reduced=True, batch=2, prompt_len=4,
                    gen=4)
    assert res.tokens.shape == (2, 8)
    assert res.supervisor.policy == "inline"
    assert res.supervisor.events == []
    assert res.collector.integrity_failures == 0

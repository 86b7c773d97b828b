"""Bounded-capacity model checker (repro.analysis.modelcheck).

The central claims under test: the verdict is *total* (every capacity map
decides to ``safe`` or ``deadlock``), every ``safe`` verdict carries the
exact completion cycle the simulator reports, every ``deadlock`` verdict
carries a certificate the simulator confirms, and ``minimize_capacities``
emits a jointly-safe, per-edge Pareto-minimal plan that never exceeds the
conservative static bounds.
"""
import numpy as np
from _hypothesis_shim import given, settings, st

from repro.analysis import (
    VERDICT_DEADLOCK, VERDICT_SAFE, analyze_sim, bounded_replay,
    check_capacities, effective_capacities, grade_decidability,
    minimize_capacities, run_lint, static_sizing_plan,
)
from repro.analysis.modelcheck import _Packed
from repro.rinn import RinnConfig, ZCU102, compile_graph, generate_rinn, run_sim
from repro.rinn.cosim import compare, run_with_remediation
from repro.rinn.streamsim import CapacityFault, FaultPlan

DEADLOCK_CFG = RinnConfig(n_backbone=5, image_size=8, seed=4, density=0.4)
DEADLOCK_PLAN = FaultPlan(seed=1, capacities=(
    CapacityFault(edge=("clone_conv1", "merge3"), capacity=2),))
FAULT_EDGE = ("clone_conv1", "merge3")


def _deadlock_setup():
    sim = compile_graph(generate_rinn(DEADLOCK_CFG), ZCU102)
    an = analyze_sim(sim)
    caps = effective_capacities(sim, DEADLOCK_PLAN)
    return sim, an, caps


# --------------------------------------------------------------------- #
# totality: every map decides, and the decision matches the simulator
# --------------------------------------------------------------------- #
def test_verdict_is_total_on_capacity_grid():
    sim, an, _ = _deadlock_setup()
    lbs = an.capacity_lower_bounds()
    grid = {
        "below": {e: max(1, lb - 1) for e, lb in lbs.items()},
        "at": dict(lbs),
        "above": {e: lb + 2 for e, lb in lbs.items()},
    }
    for caps in grid.values():
        assert an.deadlock_verdict(caps) in (VERDICT_SAFE, VERDICT_DEADLOCK)


def test_safe_verdict_carries_exact_completion_cycle():
    sim, an, _ = _deadlock_setup()
    lbs = an.capacity_lower_bounds()
    # at-bound: replay argument, exact cycle without executing a replay
    at = an.check(lbs)
    assert at.safe and at.method == "replay-argument"
    assert at.completion_cycle == an.predicted_cycles
    # below-bound but still completing: bounded replay, still exact
    tight = {e: max(1, lb - 1) for e, lb in lbs.items()}
    dec = an.check(tight)
    res = run_sim(sim, capacity_overrides=tight, max_cycles=50_000)
    if dec.safe:
        assert dec.method == "bounded-replay"
        assert res.completed and res.cycles == dec.completion_cycle
    else:
        assert not res.completed


def test_deadlock_certificate_replays_to_confirmed_stall():
    sim, an, caps = _deadlock_setup()
    dec = an.check(caps)
    assert dec.verdict == VERDICT_DEADLOCK and dec.completion_cycle is None
    cert = dec.certificate
    assert cert is not None and cert.confirm(sim)
    # the blocking cycle is well-formed: non-empty, closed, and each wait
    # is a true blocker at the fixpoint (full at capacity or empty)
    assert cert.cycle, cert.summary()
    actors = [w.actor for w in cert.cycle]
    assert cert.cycle[-1].waits_on == actors[0]
    for w, nxt in zip(cert.cycle, actors[1:] + actors[:1]):
        assert w.waits_on == nxt
        if w.kind == "full":
            assert w.occupancy >= w.capacity
        else:
            assert w.occupancy == 0
    # the faulted FIFO is among the blocked edges
    assert FAULT_EDGE in cert.blocked_edges
    # serialization round-trips the cycle
    doc = cert.to_dict()
    assert doc["stall_cycle"] == cert.stall_cycle
    assert len(doc["cycle"]) == len(cert.cycle)


def test_certificate_confirm_rejects_wrong_state():
    sim, an, caps = _deadlock_setup()
    cert = an.check(caps).certificate
    # a certificate for a *different* capacity map must not confirm:
    # growing the faulted FIFO to its bound completes the run
    import dataclasses

    fixed = dict(cert.capacities)
    fixed[FAULT_EDGE] = an.bounds[FAULT_EDGE].capacity_lb
    wrong = dataclasses.replace(cert, capacities=fixed)
    assert not wrong.confirm(sim)


@settings(deadline=None, max_examples=10)
@given(st.integers(0, 10_000), st.integers(3, 7),
       st.sampled_from(["density", "short_skip", "long_skip", "ends_only"]))
def test_checker_agrees_with_simulator_on_random_maps(seed, depth, pattern):
    """Property: on randomized small graphs x randomized capacity maps the
    total verdict always matches run_sim ground truth — safe verdicts
    complete at exactly the predicted cycle, deadlock certificates replay
    to the certified stall."""
    cfg = RinnConfig(n_backbone=depth, image_size=8, seed=seed,
                     pattern=pattern, density=0.4)
    sim = compile_graph(generate_rinn(cfg), ZCU102)
    an = analyze_sim(sim)
    rng = np.random.default_rng(seed)
    lbs = an.capacity_lower_bounds()
    caps = {e: int(rng.integers(1, lb + 3)) for e, lb in lbs.items()}
    dec = check_capacities(sim, caps, analysis=an)
    res = run_sim(sim, capacity_overrides=caps, max_cycles=100_000)
    if dec.safe:
        assert res.completed and res.cycles == dec.completion_cycle
    else:
        assert not res.completed
        assert dec.certificate.confirm(sim)


@settings(deadline=None, max_examples=6)
@given(st.integers(0, 10_000), st.integers(3, 6))
def test_checker_agrees_with_simulator_profiled(seed, depth):
    """Property: ditto under Listing-2 profiling interference (the replay
    argument does not apply there, so every map goes through the exact
    bounded replay)."""
    cfg = RinnConfig(n_backbone=depth, image_size=8, seed=seed, density=0.4)
    sim = compile_graph(generate_rinn(cfg), ZCU102)
    an = analyze_sim(sim)
    rng = np.random.default_rng(seed + 1)
    caps = {e: int(rng.integers(1, lb + 3))
            for e, lb in an.capacity_lower_bounds().items()}
    dec = check_capacities(sim, caps, profiled=True, analysis=an)
    assert dec.method == "bounded-replay"
    res = run_sim(sim, profiled=True, capacity_overrides=caps,
                  max_cycles=100_000)
    if dec.safe:
        assert res.completed and res.cycles == dec.completion_cycle
    else:
        assert not res.completed
        assert dec.certificate.confirm(sim)


def test_check_results_are_memoized():
    _, an, caps = _deadlock_setup()
    assert an.check(caps) is an.check(dict(caps))
    assert an.check(caps) is not an.check(caps, profiled=True)


# --------------------------------------------------------------------- #
# exact minimal capacity synthesis
# --------------------------------------------------------------------- #
def test_minimize_never_exceeds_conservative_bounds():
    sim, an, _ = _deadlock_setup()
    plan = minimize_capacities(an)
    for e in plan.minimal:
        assert plan.minimal[e] <= plan.conservative[e], e
        assert plan.minimal[e] >= 1
    assert check_capacities(sim, plan.minimal, analysis=an).safe


def test_minimize_is_pareto_minimal():
    """Lowering any single edge of the minimal map by one word deadlocks."""
    sim, an, _ = _deadlock_setup()
    plan = minimize_capacities(an)
    packed = _Packed(sim, False)
    for e in sim.edge_list:
        if plan.minimal[e] <= 1:
            continue
        probe = dict(plan.minimal)
        probe[e] -= 1
        assert not bounded_replay(sim, probe, _packed=packed).completed, e


def test_minimize_plan_seeds_remediation_with_zero_attempts():
    """The acceptance criterion: the exact plan clears the trace_smoke
    capacity-fault deadlock with zero ladder attempts."""
    sim, an, _ = _deadlock_setup()
    plan = static_sizing_plan(an, faults=DEADLOCK_PLAN, exact=True)
    seed = plan.capacity_map()
    assert FAULT_EDGE in seed
    res, attempts = run_with_remediation(
        sim, profiled=True, max_cycles=50_000, faults=DEADLOCK_PLAN,
        initial_overrides=seed)
    assert res.completed and attempts == []


def test_minimize_profiled_is_safe_under_interference():
    sim, an, _ = _deadlock_setup()
    plan = minimize_capacities(an, profiled=True)
    res = run_sim(sim, profiled=True, max_cycles=50_000,
                  capacity_overrides=plan.minimal)
    assert res.completed


def test_exact_plan_advice_vs_configured_capacities():
    sim, an, _ = _deadlock_setup()
    plan = static_sizing_plan(an, faults=DEADLOCK_PLAN, exact=True)
    grown = {a.edge: a.recommended for a in plan.grown}
    assert FAULT_EDGE in grown
    assert grown[FAULT_EDGE] <= an.bounds[FAULT_EDGE].capacity_lb
    # everything else sits at the generous default: shrink advisories only
    for a in plan.shrunk:
        assert a.recommended == plan.minimal[a.edge]
    assert plan.words_saved_vs_bound >= 0
    assert plan.best_ratio >= 1.0
    assert "exact sizing" in plan.summary()


# --------------------------------------------------------------------- #
# remediation precheck + cosim report wiring
# --------------------------------------------------------------------- #
def test_static_precheck_skips_ladder_entirely():
    sim, _, _ = _deadlock_setup()
    res, attempts = run_with_remediation(
        sim, profiled=True, max_cycles=50_000, faults=DEADLOCK_PLAN,
        static_precheck=True)
    assert res.completed and attempts == []
    # without the precheck the same scenario needs the ladder
    res0, attempts0 = run_with_remediation(
        sim, profiled=True, max_cycles=50_000, faults=DEADLOCK_PLAN)
    assert attempts0


def test_static_precheck_on_safe_config_changes_nothing():
    sim, _, _ = _deadlock_setup()
    res, attempts = run_with_remediation(sim, static_precheck=True)
    assert res.completed and attempts == []
    base = run_sim(sim)
    assert res.cycles == base.cycles


def test_compare_attaches_verdict_and_certificate():
    g = generate_rinn(DEADLOCK_CFG)
    rep = compare(g, ZCU102, faults=DEADLOCK_PLAN, auto_remediate=True,
                  static_check=True)
    assert rep.static_verdict == VERDICT_DEADLOCK
    assert rep.static_certificate is not None
    assert rep.static_certificate.cycle
    clean = compare(g, ZCU102, static_check=True)
    assert clean.static_verdict == VERDICT_SAFE
    assert clean.static_certificate is None


# --------------------------------------------------------------------- #
# decidability grading
# --------------------------------------------------------------------- #
def test_grade_decidability_confirms_against_simulator():
    _, an, caps = _deadlock_setup()
    lbs = an.capacity_lower_bounds()
    grid = {
        "faulted": caps,
        "at": dict(lbs),
        "above": {e: lb + 2 for e, lb in lbs.items()},
    }
    grade = grade_decidability(an, grid, confirm=True, max_cycles=50_000)
    assert grade.decided_fraction == 1.0
    assert grade.confirmed_fraction == 1.0
    assert not grade.undecided and not grade.misdecided
    by_label = {o.label: o for o in grade.outcomes}
    assert by_label["faulted"].verdict == VERDICT_DEADLOCK
    assert by_label["at"].verdict == VERDICT_SAFE
    assert "decided 1.00" in grade.summary()


# --------------------------------------------------------------------- #
# lint rules RINN008 (certificate-citing), RINN012, RINN013
# --------------------------------------------------------------------- #
def test_rinn008_cites_certificate_cycle():
    g = generate_rinn(DEADLOCK_CFG)
    rep = run_lint(g, timing=ZCU102, faults=DEADLOCK_PLAN)
    hits = [f for f in rep.findings if f.rule == "RINN008"]
    assert len(hits) == 1 and hits[0].edge == FAULT_EDGE
    assert "blocking cycle" in hits[0].message
    assert "fixpoint at cycle" in hits[0].message


def test_rinn012_flags_dangling_override_edges():
    g = generate_rinn(DEADLOCK_CFG)
    rep = run_lint(g, overrides={("nonexistent", "merge3"): 8,
                                 ("conv2", "clone_conv1"): 4})
    hits = {f.edge: f for f in rep.findings if f.rule == "RINN012"}
    assert set(hits) == {("nonexistent", "merge3"),
                         ("conv2", "clone_conv1")}
    # a near-miss between real nodes suggests real edges
    assert "did you mean" in hits[("conv2", "clone_conv1")].hint
    # a bogus node name is called out directly
    assert "nonexistent" in hits[("nonexistent", "merge3")].hint


def test_rinn012_flags_dangling_capacity_faults():
    g = generate_rinn(DEADLOCK_CFG)
    plan = FaultPlan(seed=0, capacities=(
        CapacityFault(edge=("ghost", "merge3"), capacity=2),))
    rep = run_lint(g, faults=plan)
    assert any(f.rule == "RINN012" for f in rep.findings)
    # valid edges never fire it
    clean = run_lint(g, faults=DEADLOCK_PLAN,
                     overrides={FAULT_EDGE: 64})
    assert not [f for f in clean.findings if f.rule == "RINN012"]


def test_rinn013_needs_exact_opt_in():
    g = generate_rinn(DEADLOCK_CFG)
    off = run_lint(g, timing=ZCU102)
    assert "RINN013" in off.skipped
    on = run_lint(g, timing=ZCU102, exact=True)
    assert "RINN013" in on.ran
    hits = [f for f in on.findings if f.rule == "RINN013"]
    assert hits  # bound 2 vs minimal 1 edges exist on this design
    for f in hits:
        assert "exact minimal capacity" in f.message


# --------------------------------------------------------------------- #
# CLI flags
# --------------------------------------------------------------------- #
def test_cli_minimize_and_certificate(capsys, tmp_path):
    import json

    from repro.analysis.__main__ import main

    out = tmp_path / "findings.json"
    rc = main(["--demo-fault", "--minimize", "--certificate",
               "--rules", "RINN008,RINN013", "--out", str(out)])
    assert rc == 1  # the demo fault is an ERROR
    doc = json.loads(out.read_text())
    faulted = [d for d in doc["designs"] if d["design"].endswith("capfault")]
    assert len(faulted) == 1
    d = faulted[0]
    assert d["verdict"] == VERDICT_DEADLOCK
    assert d["certificate"]["cycle"]
    assert d["minimize"]["words_saved"] >= 0
    assert d["minimize"]["minimal_words"] <= d["minimize"]["conservative_words"]
    for other in doc["designs"]:
        if other is not d:
            assert other["verdict"] == VERDICT_SAFE
            assert other["completion_cycle"] is not None
    text = capsys.readouterr().out
    assert "certificate: fixpoint at cycle" in text
    assert "minimize:" in text

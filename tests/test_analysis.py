"""Static dataflow analysis + lint framework (repro.analysis)."""
import json

import pytest
from _hypothesis_shim import given, settings, st

from repro.analysis import (
    ERROR, RULES, VERDICT_DEADLOCK, VERDICT_SAFE, analyze_graph, analyze_sim,
    effective_capacities, grade_saturation, run_lint, static_sizing_plan,
)
from repro.rinn import (RinnConfig, ZCU102, compile_graph, generate_rinn, run_sim)
from repro.rinn.cosim import compare, run_with_remediation
from repro.rinn.layers import ReluSpec
from repro.rinn.streamsim import CapacityFault, FaultPlan
from repro.trace import recommend_capacities, trace_run, diff_traces

DEADLOCK_CFG = RinnConfig(n_backbone=5, image_size=8, seed=4, density=0.4)
DEADLOCK_PLAN = FaultPlan(seed=1, capacities=(
    CapacityFault(edge=("clone_conv1", "merge3"), capacity=2),))


# --------------------------------------------------------------------- #
# the unbounded schedule is exact
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("cfg", [
    RinnConfig(n_backbone=6, image_size=8, seed=1, density=0.4),
    RinnConfig(family="dense", n_backbone=6, seed=2, pattern="long_skip",
               density=0.4),
    RinnConfig(n_backbone=8, image_size=8, seed=0, pattern="ends_only"),
])
def test_static_schedule_matches_simulator(cfg):
    sim = compile_graph(generate_rinn(cfg), ZCU102)
    an = analyze_sim(sim)
    res = run_sim(sim, profiled=False)
    assert res.completed
    assert an.predicted_cycles == res.cycles
    for e, b in an.bounds.items():
        assert b.peak_backlog == res.fifo_max[e], e


def test_capacity_lb_replays_schedule_exactly():
    """Capping every FIFO at its static bound must not perturb the run."""
    sim = compile_graph(generate_rinn(DEADLOCK_CFG), ZCU102)
    an = analyze_sim(sim)
    lbs = an.capacity_lower_bounds()
    res = run_sim(sim, profiled=False, capacity_overrides=lbs)
    assert res.completed and res.cycles == an.predicted_cycles
    # ... and at exactly the bound the predicted saturation set is exact
    obs = {e for e in sim.edge_list if res.fifo_max[e] >= lbs[e]}
    assert {b.edge for b in an.predicted_saturated(lbs)} == obs


def test_throughput_bound_names_busiest_actor():
    an = analyze_graph(generate_rinn(DEADLOCK_CFG), ZCU102)
    tp = an.throughput()
    assert tp.predicted_cycles == an.predicted_cycles
    assert tp.bottleneck_node in an.schedules
    assert tp.bottleneck_span == max(tp.node_spans.values())


# --------------------------------------------------------------------- #
# deadlock verdicts + zero-attempt static seeding (the acceptance path)
# --------------------------------------------------------------------- #
def test_static_verdicts_on_fault_scenario():
    sim = compile_graph(generate_rinn(DEADLOCK_CFG), ZCU102)
    an = analyze_sim(sim)
    assert an.deadlock_verdict(effective_capacities(sim)) == VERDICT_SAFE
    caps = effective_capacities(sim, DEADLOCK_PLAN)
    assert an.deadlock_verdict(caps) == VERDICT_DEADLOCK


def test_static_seed_clears_deadlock_with_zero_attempts():
    """Static bounds alone must clear the capacity fault: no ladder, no
    prior trace."""
    sim = compile_graph(generate_rinn(DEADLOCK_CFG), ZCU102)
    an = analyze_sim(sim)
    plan = static_sizing_plan(an, faults=DEADLOCK_PLAN)
    seed = plan.capacity_map()
    assert seed  # the faulted edge got a grow
    res, attempts = run_with_remediation(
        sim, profiled=True, max_cycles=50_000, faults=DEADLOCK_PLAN,
        initial_overrides=seed)
    assert res.completed and attempts == []
    # sanity: without the seed the fault does deadlock into the ladder
    res0, attempts0 = run_with_remediation(
        sim, profiled=True, max_cycles=50_000, faults=DEADLOCK_PLAN)
    assert attempts0


@settings(deadline=None, max_examples=8)
@given(st.integers(0, 10_000), st.integers(3, 7),
       st.sampled_from(["density", "short_skip", "long_skip", "ends_only"]),
       st.integers(0, 3))
def test_safe_verdict_never_deadlocks(seed, depth, pattern, slack):
    """Property: capacities meeting the static bounds => the bounded run
    completes (and replays the unbounded schedule exactly)."""
    cfg = RinnConfig(n_backbone=depth, image_size=8, seed=seed,
                     pattern=pattern, density=0.4)
    sim = compile_graph(generate_rinn(cfg), ZCU102)
    an = analyze_sim(sim)
    caps = {e: lb + slack for e, lb in an.capacity_lower_bounds().items()}
    assert an.deadlock_verdict(caps) == VERDICT_SAFE
    res = run_sim(sim, profiled=False, capacity_overrides=caps)
    assert res.completed and res.cycles == an.predicted_cycles


@settings(deadline=None, max_examples=6)
@given(st.integers(0, 10_000), st.integers(4, 7))
def test_deadlock_verdict_implies_stall(seed, depth):
    """Property: a ``deadlock`` verdict is a guarantee — the run must not
    complete.  (Not every config yields a provable deadlock; only verdicts
    that fire are checked.)"""
    cfg = RinnConfig(n_backbone=depth, image_size=8, seed=seed, density=0.5)
    sim = compile_graph(generate_rinn(cfg), ZCU102)
    an = analyze_sim(sim)
    merges = [n for n in sim.node_ids
              if len([1 for (s, d) in sim.edge_list if d == n]) >= 2]
    if not merges:
        return
    victim = next(e for e in sim.edge_list if e[1] == merges[-1])
    caps = effective_capacities(sim, FaultPlan(seed=0, capacities=(
        CapacityFault(edge=victim, capacity=2),)))
    if an.deadlock_verdict(caps) != VERDICT_DEADLOCK:
        return
    res = run_sim(sim, profiled=False, max_cycles=30_000,
                  capacity_overrides=caps)
    assert not res.completed


@settings(deadline=None, max_examples=6)
@given(st.integers(0, 10_000),
       st.sampled_from(["density", "long_skip", "ends_only"]))
def test_static_bound_never_exceeds_trace_recommendation(seed, pattern):
    """Property: the static capacity bound is a true minimum — it never
    exceeds what trace-driven sizing recommends from an observed run."""
    cfg = RinnConfig(n_backbone=6, image_size=8, seed=seed, pattern=pattern,
                     density=0.4)
    sim = compile_graph(generate_rinn(cfg), ZCU102)
    an = analyze_sim(sim)
    _, store = trace_run(sim, profiled=False, windows=32)
    plan = recommend_capacities(store, sim)
    rec = plan.capacity_map(include_shrink=True)
    for e, lb in an.capacity_lower_bounds().items():
        if e in rec:
            assert lb <= rec[e], e


# --------------------------------------------------------------------- #
# lint rules
# --------------------------------------------------------------------- #
def _broken_graph():
    g = generate_rinn(DEADLOCK_CFG)
    g.edges.append(g.edges[3])                    # duplicate
    g.nodes["orphan"] = ReluSpec(name="orphan")   # unreachable + dead end
    g.nodes["dangler"] = ReluSpec(name="dangler")
    g.edges.append(("conv0", "dangler"))          # dead end
    return g


def test_lint_topology_rules_fire_on_broken_graph():
    rep = run_lint(_broken_graph())
    rules = {f.rule for f in rep.findings}
    assert {"RINN001", "RINN002", "RINN003"} <= rules
    assert not rep.ok
    orphan = [f for f in rep.findings if f.node == "orphan"]
    assert any(f.rule == "RINN001" for f in orphan)


def test_lint_self_loop_rule():
    g = generate_rinn(DEADLOCK_CFG)
    g.edges.append(("conv2", "conv2"))
    rep = run_lint(g, rules=["RINN004"])
    assert [f.rule for f in rep.findings] == ["RINN004"]
    assert rep.findings[0].edge == ("conv2", "conv2")


def test_lint_capacity_rules_on_fault_plan():
    g = generate_rinn(DEADLOCK_CFG)
    rep = run_lint(g, timing=ZCU102, faults=DEADLOCK_PLAN)
    hits = [f for f in rep.findings if f.rule == "RINN008"]
    assert len(hits) == 1 and hits[0].severity == ERROR
    assert hits[0].edge == ("clone_conv1", "merge3")
    assert "grow to" in hits[0].hint
    # healthy config: no capacity errors, over-provision advisory instead
    rep2 = run_lint(g, timing=ZCU102)
    assert rep2.ok
    assert any(f.rule == "RINN011" for f in rep2.findings)


def test_lint_guard_mixing_rule():
    import jax.numpy as jnp
    from repro.core.stream import ProfileStream

    s = ProfileStream.create()
    s = s.append_guarded("a", "fifo", jnp.ones(3), algo="xor24")
    s = s.append_guarded("b", "fifo", jnp.ones(3), algo="crc32")
    g = generate_rinn(DEADLOCK_CFG)
    rep = run_lint(g, stream=s, rules=["RINN010"])
    assert [f.rule for f in rep.findings] == ["RINN010"]
    # single-algo stream is clean
    s1 = ProfileStream.create().append_guarded("a", "fifo", jnp.ones(3))
    assert run_lint(g, stream=s1, rules=["RINN010"]).ok


def test_lint_skips_inapplicable_rules():
    rep = run_lint(generate_rinn(DEADLOCK_CFG))
    assert "RINN008" in rep.skipped and "RINN008" not in rep.ran
    assert "RINN001" in rep.ran


def test_lint_report_roundtrips_to_json():
    rep = run_lint(_broken_graph())
    doc = json.loads(rep.to_json())
    assert doc["ok"] is False
    assert doc["counts"]["ERROR"] == len(rep.errors)
    assert all({"rule", "severity", "locus", "message"} <= set(f)
               for f in doc["findings"])


def test_rule_registry_is_complete():
    assert len(RULES) >= 8
    assert all(rid.startswith("RINN") for rid in RULES)


# --------------------------------------------------------------------- #
# validate() tightening
# --------------------------------------------------------------------- #
def test_validate_rejects_duplicate_edge():
    g = generate_rinn(DEADLOCK_CFG)
    g.edges.append(g.edges[3])
    with pytest.raises(ValueError, match="duplicate edge"):
        g.validate()


def test_validate_rejects_self_loop():
    g = generate_rinn(DEADLOCK_CFG)
    g.edges.append(("conv2", "conv2"))
    with pytest.raises(ValueError, match="self-loop"):
        g.validate()


def test_validate_rejects_unreachable_node():
    g = generate_rinn(DEADLOCK_CFG)
    g.nodes["orphan"] = ReluSpec(name="orphan")
    with pytest.raises(ValueError, match="unreachable"):
        g.validate()


def test_generated_graphs_still_validate():
    for seed in range(4):
        generate_rinn(RinnConfig(n_backbone=6, seed=seed,
                                 density=0.5)).validate()


# --------------------------------------------------------------------- #
# grading static predictions against traces
# --------------------------------------------------------------------- #
def test_grader_is_exact_on_lb_capped_run():
    cfg = RinnConfig(n_backbone=8, pattern="long_skip", image_size=8, seed=0)
    sim = compile_graph(generate_rinn(cfg), ZCU102)
    an = analyze_sim(sim)
    lbs = an.capacity_lower_bounds()
    over = {e: (lb if i % 2 == 0 else lb + 2)
            for i, (e, lb) in enumerate(sorted(lbs.items()))}
    _, store = trace_run(sim, profiled=False, capacity_overrides=over,
                         windows=32)
    grade = grade_saturation(an, store,
                             capacities=effective_capacities(
                                 sim, overrides=over))
    assert grade.precision == 1.0 and grade.recall == 1.0
    assert grade.true_pos  # something actually saturated
    assert "precision 1.00" in grade.summary()


def test_grader_localizes_false_negatives():
    """Lying to the grader about the capacities produces FNs that carry
    the windows where saturation was actually observed."""
    cfg = RinnConfig(n_backbone=8, pattern="long_skip", image_size=8, seed=0)
    sim = compile_graph(generate_rinn(cfg), ZCU102)
    an = analyze_sim(sim)
    lbs = an.capacity_lower_bounds()
    _, store = trace_run(sim, profiled=False, capacity_overrides=lbs,
                         windows=32)
    # pretend the capacities were huge: nothing is predicted to saturate
    fake = {e: 4096 for e in lbs}
    grade = grade_saturation(an, store, capacities=fake)
    assert grade.false_neg
    assert all(o.windows for o in grade.false_neg)


# --------------------------------------------------------------------- #
# window-level trace diffing
# --------------------------------------------------------------------- #
def test_diff_traces_localizes_divergence():
    sim = compile_graph(generate_rinn(DEADLOCK_CFG), ZCU102)
    _, a = trace_run(sim, profiled=False, windows=32)
    an = analyze_sim(sim)
    _, b = trace_run(sim, profiled=False, windows=32,
                     capacity_overrides=an.capacity_lower_bounds())
    diff = diff_traces(a, b, window_level=True)
    moved = [d for d in diff.deltas if d.windows]
    assert moved, "capacity squeeze must move some timeline"
    d = moved[0]
    assert d.first_divergence == d.windows[0] <= d.last_divergence
    assert d.locate().startswith("w")
    assert f"@ {d.locate()}" in diff.summary()
    # identical traces: localization finds nothing
    _, a2 = trace_run(sim, profiled=False, windows=32)
    clean = diff_traces(a, a2, window_level=True)
    assert all(not d.windows for d in clean.deltas)
    # aggregate-only mode keeps windows=None
    assert all(d.windows is None
               for d in diff_traces(a, b).deltas)


# --------------------------------------------------------------------- #
# cosim + CLI integration
# --------------------------------------------------------------------- #
def test_compare_static_check_attaches_findings():
    rep = compare(generate_rinn(DEADLOCK_CFG), ZCU102, max_cycles=50_000,
                  faults=DEADLOCK_PLAN, auto_remediate=True,
                  static_check=True)
    assert rep.completed
    assert any(f.rule == "RINN008" for f in rep.static_findings)
    assert rep.static_errors
    rep2 = compare(generate_rinn(DEADLOCK_CFG), ZCU102, max_cycles=50_000)
    assert rep2.static_findings == []


def test_cli_gate_green_on_healthy_suite(tmp_path, capsys):
    from repro.analysis.__main__ import main

    out = tmp_path / "findings.json"
    assert main(["--out", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert doc["ok"] and doc["totals"]["ERROR"] == 0
    assert len(doc["designs"]) >= 10
    assert "design(s)" in capsys.readouterr().out


def test_cli_gate_red_on_demo_fault(tmp_path, capsys):
    from repro.analysis.__main__ import main

    out = tmp_path / "findings.json"
    assert main(["--demo-fault", "--json", "--out", str(out)]) == 1
    doc = json.loads(out.read_text())
    assert not doc["ok"]
    faulty = [d for d in doc["designs"] if not d["ok"]]
    assert len(faulty) == 1 and faulty[0]["verdict"] == "deadlock"
    assert any(f["rule"] == "RINN008" for f in faulty[0]["findings"])
    capsys.readouterr()

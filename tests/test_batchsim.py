"""Compile-once, batch-many runtime tests.

Covers the PR-7 acceptance criteria: batched-vs-sequential bit-identical
results, compile-cache hits across fault plans / capacity overrides /
``profiled`` flags (trace-counter assertions), the remediation ladder
matching a rung-by-rung reference loop, the shared-capacity ``compare``
fix, multi-machine bucketed sweeps, and critical-path fault biasing.
"""
import pytest

from repro.rinn import (
    BeatFault, CapacityFault, FaultPlan, RinnConfig, ZCU102, compare,
    compile_graph, compile_stats, cosim_many, critical_path_actors,
    critical_path_edges, diagnose, generate_rinn, machine_bucket, run_sim,
    run_sim_batch, run_sim_many, run_with_remediation,
)
from repro.rinn.cosim import _ladder_overrides


def skip_cfg(seed=1, n_backbone=6, **kw):
    base = dict(family="conv", n_backbone=n_backbone, image_size=6,
                filters=2, kernel=3, pattern="long_skip", density=0.3,
                seed=seed)
    base.update(kw)
    return RinnConfig(**base)


@pytest.fixture(scope="module")
def sim():
    return compile_graph(generate_rinn(skip_cfg()), ZCU102)


@pytest.fixture(scope="module")
def sim4():
    return compile_graph(generate_rinn(skip_cfg()),
                         ZCU102.with_(fifo_capacity=4))


def assert_same_result(a, b):
    assert a.completed == b.completed
    assert a.cycles == b.cycles
    assert a.fifo_max == b.fifo_max
    assert a.fifo_profiled == b.fifo_profiled
    assert a.fifo_final == b.fifo_final
    assert a.node_consumed == b.node_consumed
    assert a.node_produced == b.node_produced


# --------------------------------------------------------------------- #
# batched == sequential, bit-identical
# --------------------------------------------------------------------- #
def test_batch_matches_sequential_bit_identical(sim):
    plans = [FaultPlan.generate(sim, seed=s, n_stalls=1, n_corruptions=1)
             for s in range(4)]
    seq = [run_sim(sim, profiled=True, faults=p) for p in plans]
    bat = run_sim_batch(sim, plans=plans, profiled=True)
    assert len(bat) == 4
    for a, b in zip(seq, bat):
        assert_same_result(a, b)


def test_batch_mixed_profiled_axis(sim):
    ref, prof = run_sim_batch(sim, plans=[None, None],
                              profiled=[False, True])
    assert_same_result(ref, run_sim(sim, profiled=False))
    assert_same_result(prof, run_sim(sim, profiled=True))
    assert not ref.fifo_profiled and prof.fifo_profiled


def test_batch_deadlock_lane_does_not_poison_others(sim):
    # lane 1 drops a beat (starves downstream); lanes 0/2 must be clean
    e = sim.edge_list[2]
    bad = FaultPlan(drops=(BeatFault(edge=e, beat=3),))
    r0, r1, r2 = run_sim_batch(sim, plans=[None, bad, None],
                               max_cycles=50_000)
    assert r0.completed and r2.completed and not r1.completed
    assert r1.deadlocked
    assert_same_result(r0, run_sim(sim, max_cycles=50_000))
    assert_same_result(
        r1, run_sim(sim, faults=bad, max_cycles=50_000))


def test_batch_capacity_override_lanes(sim4):
    base = run_sim(sim4, max_cycles=20_000)
    assert not base.completed
    grow = {e: 64 for e in sim4.edge_list}
    r_small, r_big = run_sim_batch(
        sim4, capacity_overrides=[None, grow], max_cycles=20_000)
    assert not r_small.completed and r_big.completed
    assert r_big.fifo_capacity[sim4.edge_list[0]] == 64


# --------------------------------------------------------------------- #
# compile cache: changing runtime inputs must not re-trace
# --------------------------------------------------------------------- #
def test_no_recompile_across_plans_overrides_profiled_flags(sim):
    run_sim(sim, faults=FaultPlan.generate(sim, seed=0))  # warm the cache
    t0 = compile_stats()["traces"]
    run_sim(sim, faults=FaultPlan.generate(sim, seed=1))
    run_sim(sim, faults=FaultPlan.generate(sim, seed=2, n_drops=1,
                                           n_dups=1))
    run_sim(sim, profiled=True)
    run_sim(sim, capacity_overrides={sim.edge_list[0]: 64})
    run_sim(sim, max_cycles=50_000)
    run_sim(sim, faults=FaultPlan(capacities=(
        CapacityFault(edge=sim.edge_list[1], capacity=2),)),
        max_cycles=20_000)
    assert compile_stats()["traces"] == t0, \
        "runtime inputs leaked into the trace — executable recompiled"


def test_no_recompile_across_same_bucket_graphs():
    g1 = generate_rinn(skip_cfg(seed=0))
    g2 = generate_rinn(skip_cfg(seed=2))
    s1, s2 = compile_graph(g1, ZCU102), compile_graph(g2, ZCU102)
    if machine_bucket(s1) != machine_bucket(s2):
        pytest.skip("seeds drew different shape buckets")
    run_sim(s1)
    t0 = compile_stats()["traces"]
    run_sim(s2)
    assert compile_stats()["traces"] == t0


def test_batch_launch_counts(sim):
    plans = [FaultPlan.generate(sim, seed=s) for s in range(3)]
    before = compile_stats()
    run_sim_batch(sim, plans=plans)
    after = compile_stats()
    assert after["launches"] == before["launches"] + 1
    assert after["lanes"] == before["lanes"] + 3


# --------------------------------------------------------------------- #
# the remediation ladder == a rung-by-rung loop of run_sim
# --------------------------------------------------------------------- #
def _reference_ladder(sim, faults, budget=6, growth=2):
    """Grow-and-retry with one ``run_sim`` a rung; attempts as
    ``(attempt, overrides, completed)``."""
    node_of = {nid: i for i, nid in enumerate(sim.node_ids)}
    bound = {e: max(2, int(sim.total_out[node_of[e[0]]]))
             for e in sim.edge_list}
    base = {e: sim.capacity for e in sim.edge_list}
    base.update({cf.edge: cf.capacity
                 for cf in (faults.capacities if faults else ())})
    ever_full, attempts = set(), []
    res = run_sim(sim, faults=faults)
    for k in range(budget):
        if res.completed:
            break
        report = diagnose(sim, res)
        if not report.capacity_induced:
            attempts.append((k, {}, False))
            break
        ever_full |= {e for e in sim.edge_list
                      if any(e[1] == f[1] for f in report.full_edges)}
        overrides = _ladder_overrides(ever_full, bound, base, growth, k + 1)
        res = run_sim(sim, faults=faults, capacity_overrides=overrides)
        attempts.append((k, overrides, res.completed))
    return res, attempts


@pytest.mark.parametrize("case", ["small_fifos", "fault_capacity"])
def test_remediation_matches_reference_ladder(sim, sim4, case):
    if case == "small_fifos":
        machine, plan = sim4, None
    else:
        base = run_sim(sim)
        edge = max(base.fifo_max, key=base.fifo_max.get)
        machine = sim
        plan = FaultPlan(capacities=(CapacityFault(edge=edge, capacity=1),))
    res, attempts = run_with_remediation(machine, faults=plan)
    ref, ref_attempts = _reference_ladder(machine, plan)
    assert ref_attempts, "the ladder must fire"
    assert res.completed == ref.completed
    assert res.cycles == ref.cycles
    assert res.fifo_max == ref.fifo_max
    assert res.fifo_capacity == ref.fifo_capacity
    assert [(a.attempt, a.overrides, a.completed)
            for a in attempts] == ref_attempts
    if case == "small_fifos":
        assert res.completed


def test_remediation_ladder_gives_up_on_starvation(sim):
    e = sim.edge_list[2]
    plan = FaultPlan(drops=(BeatFault(edge=e, beat=3),))
    res, attempts = run_with_remediation(sim, faults=plan)
    assert not res.completed
    assert len(attempts) == 1  # one diagnosis, no futile sizing attempts
    assert not attempts[-1].report.capacity_induced


# --------------------------------------------------------------------- #
# compare(): batched pair + one shared remediated capacity map
# --------------------------------------------------------------------- #
def test_compare_auto_remediate_shares_one_capacity_map():
    g = generate_rinn(skip_cfg())
    timing = ZCU102.with_(fifo_capacity=4)
    rep = compare(g, timing, max_cycles=20_000, auto_remediate=True)
    assert rep.completed and rep.remediation
    caps = rep.remediated_capacities
    assert caps and all(c > 4 for c in caps.values())
    # both columns of every row must come from THIS capacity map: re-running
    # each side under the shared map reproduces the table exactly
    sim = compile_graph(g, timing)
    ref = run_sim(sim, max_cycles=20_000, capacity_overrides=caps)
    prof = run_sim(sim, profiled=True, max_cycles=20_000,
                   capacity_overrides=caps)
    assert ref.completed and prof.completed
    for row in rep.rows:
        assert row.cosim == ref.fifo_max[row.edge]
        assert row.profiled == prof.fifo_profiled[row.edge]


def test_compare_without_remediation_unchanged():
    g = generate_rinn(skip_cfg())
    rep = compare(g, ZCU102)
    assert rep.completed and not rep.remediated_capacities
    sim = compile_graph(g, ZCU102)
    ref = run_sim(sim)
    prof = run_sim(sim, profiled=True)
    for row in rep.rows:
        assert row.cosim == ref.fifo_max[row.edge]
        assert row.profiled == prof.fifo_profiled[row.edge]


# --------------------------------------------------------------------- #
# multi-machine sweeps
# --------------------------------------------------------------------- #
def test_run_sim_many_matches_singles_across_sizes():
    sims = [compile_graph(generate_rinn(skip_cfg(seed=7, n_backbone=n)),
                          ZCU102) for n in (4, 5, 6)]
    many = run_sim_many(sims)
    for s, r in zip(sims, many):
        assert_same_result(r, run_sim(s))


def test_cosim_many_reports_deadlocks_without_raising():
    graphs = [generate_rinn(skip_cfg(seed=s)) for s in (1, 2)]
    results = cosim_many(graphs, ZCU102.with_(fifo_capacity=4),
                         max_cycles=20_000)
    assert len(results) == 2
    deadlocked = [(res, rep) for res, rep in results if rep is not None]
    assert deadlocked, "capacity-4 long-skip graphs should stall"
    for res, rep in deadlocked:
        assert not res.completed
        assert rep.blocked and "deadlock" in rep.summary()
    # healthy timing: every report slot is None
    ok = cosim_many(graphs, ZCU102)
    assert all(rep is None and res.completed for res, rep in ok)


# --------------------------------------------------------------------- #
# critical-path fault biasing
# --------------------------------------------------------------------- #
def test_fault_bias_critical_path_targets_heavy_actors(sim):
    plan = FaultPlan.generate(sim, seed=7, n_stalls=5, n_corruptions=3,
                              bias="critical_path")
    hot_nodes = set(critical_path_actors(sim))
    assert {s.node for s in plan.stalls} <= hot_nodes
    node_of = {nid: i for i, nid in enumerate(sim.node_ids)}
    prof_edges = [e for e in sim.edge_list
                  if sim.profiled[node_of[e[1]]]] or list(sim.edge_list)
    hot_edges = set(critical_path_edges(sim, prof_edges))
    assert {c.edge for c in plan.corruptions} <= hot_edges


def test_fault_bias_uniform_is_default_and_unchanged(sim):
    p_default = FaultPlan.generate(sim, seed=3, n_stalls=2)
    p_uniform = FaultPlan.generate(sim, seed=3, n_stalls=2, bias="uniform")
    assert p_default == p_uniform


def test_fault_bias_rejects_unknown(sim):
    with pytest.raises(ValueError):
        FaultPlan.generate(sim, seed=0, bias="chaotic")


def test_biased_plans_are_seed_deterministic(sim):
    a = FaultPlan.generate(sim, seed=5, n_stalls=3, bias="critical_path")
    b = FaultPlan.generate(sim, seed=5, n_stalls=3, bias="critical_path")
    assert a == b

"""Traffic: the same seed gives the same pool, the mix is the same for every
seed, designs are pinned, and set-up warms every shape bucket the pool
hits."""
import json
from pathlib import Path
from unittest import mock

from bench.reference import dataflow
from bench.traffic import campaign, designs

BENCH = Path(__file__).resolve().parents[1]
CFG = json.loads((BENCH / "configs" / "rinn-t1-zcu102.json").read_text())
CAMPAIGN = json.loads((BENCH / "traffic" / "campaign-1024.json").read_text())
COSIM = json.loads((BENCH / "traffic" / "cosim-pool32.json").read_text())
BIG_SEED = 2**31 + 977


def _machine():
    graph = designs.pinned(CFG["design"], CFG["design"]["seed"],
                           CAMPAIGN["design_fingerprint"])
    return dataflow.lower(graph, CFG["timing"])


def _key(call):
    return [(repr(p), sorted(c.items()), f) for p, c, f in call]


def test_campaign_pool_is_a_function_of_the_seed():
    m = _machine()
    a = campaign.calls(m, CAMPAIGN, BIG_SEED)
    b = campaign.calls(m, CAMPAIGN, BIG_SEED)
    c = campaign.calls(m, CAMPAIGN, BIG_SEED + 1)
    assert [_key(x) for x in a] == [_key(x) for x in b]
    assert [_key(x) for x in a] != [_key(x) for x in c]


def test_campaign_mix_is_the_same_for_every_seed():
    m = _machine()
    for seed in (0, BIG_SEED):
        pool = campaign.calls(m, CAMPAIGN, seed)
        assert len(pool) == CAMPAIGN["pool_calls"]
        for call in pool:
            assert len(call) == CAMPAIGN["lanes"]
            free = call[:CAMPAIGN["fault_free"]]
            assert all(p is None for p, _, _ in free)
            assert sum(f for _, _, f in free) == CAMPAIGN["fault_free"] // 2
            faulted = call[CAMPAIGN["fault_free"]:]
            assert all(len(p.stalls) == 1 and len(p.corruptions) == 1
                       for p, _, _ in faulted)
            assert sum(len(p.drops) for p, _, _ in faulted) == len(faulted) // 4


def test_design_pool_order_and_pins():
    assert designs.order(32, BIG_SEED) == designs.order(32, BIG_SEED)
    assert sorted(designs.order(32, BIG_SEED)) == list(range(32))
    assert designs.order(32, BIG_SEED) != designs.order(32, BIG_SEED + 1)
    for s, fp in COSIM["design_fingerprints"].items():
        designs.pinned(CFG["design"], int(s), fp)


def test_a_changed_design_is_refused():
    try:
        designs.pinned(CFG["design"], 42, "0" * 16)
    except RuntimeError as e:
        assert "fingerprint" in str(e)
    else:
        raise AssertionError("a wrong fingerprint was accepted")


def test_cosim_setup_warms_every_bucket_of_the_pool():
    from repro.rinn import ZCU102, compile_graph, machine_bucket

    from bench.drivers.cosim import Driver

    pool_buckets = {
        machine_bucket(compile_graph(designs.build(CFG["design"], int(s)),
                                     ZCU102))
        for s in COSIM["design_fingerprints"]}
    warmed = []
    ctx = mock.Mock(config=CFG, traffic=COSIM, seed=BIG_SEED)
    with mock.patch("repro.rinn.compare",
                    lambda g, *a, **k: warmed.append(g)):
        Driver(ctx).setup()
    assert {machine_bucket(compile_graph(g, ZCU102)) for g in warmed} \
        == pool_buckets
    assert len(pool_buckets) > 1


def test_campaign_setup_runs_every_pool_call():
    from bench.drivers.campaign import Driver

    ran = []
    ctx = mock.Mock(config=CFG, traffic=CAMPAIGN, seed=BIG_SEED)
    with mock.patch("repro.rinn.run_sim_batch",
                    lambda sim, **k: ran.append(len(k["plans"]))):
        d = Driver(ctx)
        d.setup()
    assert ran == [CAMPAIGN["lanes"]] * CAMPAIGN["pool_calls"]

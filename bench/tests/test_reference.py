"""The plain references agree with the program where the program is right:
the NumPy machine with the simulator lane by lane, the float32 decoder with
the served model at a tiny size."""
import json
from pathlib import Path

import numpy as np

from bench.reference import chatglm, dataflow
from bench.tests._drive import TINY_GLM
from bench.traffic import campaign, designs

BENCH = Path(__file__).resolve().parents[1]
CFG = json.loads((BENCH / "configs" / "rinn-t1-zcu102.json").read_text())
CAMPAIGN = json.loads((BENCH / "traffic" / "campaign-1024.json").read_text())
GLM = {**json.loads((BENCH / "configs" / "chatglm3-6b.json").read_text()),
       **TINY_GLM}


def test_machine_matches_the_simulator_lane_by_lane():
    from repro.rinn import TimingProfile, compile_graph, run_sim_batch

    graph = designs.build(CFG["design"], CFG["design"]["seed"])
    m = dataflow.lower(graph, CFG["timing"])
    call = campaign.calls(m, CAMPAIGN, 7)[0]
    lanes = call[:24] + call[CAMPAIGN["fault_free"]:][:40]
    sim = compile_graph(graph, TimingProfile(**CFG["timing"]))
    got = run_sim_batch(sim, plans=[p for p, _, _ in lanes],
                        capacity_overrides=[c for _, c, _ in lanes],
                        profiled=[f for _, _, f in lanes])
    assert 0 < sum(r.completed for r in got) < len(got)
    for (plan, caps, prof), res in zip(lanes, got):
        ref = dataflow.simulate(m, plan=plan, capacities=caps, profiled=prof)
        assert dataflow.differs(res, ref) == []


def test_machine_matches_cosim_pairs():
    from repro.rinn import ZCU102, compare

    for seed in (42, 3, 11):
        graph = designs.build(CFG["design"], seed)
        rep = compare(graph, ZCU102, auto_remediate=True)
        m = dataflow.lower(graph, CFG["timing"])
        ref = dataflow.simulate(m)
        prof = dataflow.simulate(m, profiled=True)
        assert (rep.cycles_unprofiled, rep.cycles_profiled) == (
            ref["cycles"], prof["cycles"])
        assert {r.edge: (r.cosim, r.profiled) for r in rep.rows} == {
            e: (ref["fifo_max"][e], v) for e, v in prof["fifo_profiled"].items()}


def test_reference_weights_are_the_served_weights():
    import jax

    from repro.configs import get_config
    from repro.models import init_params
    from repro.models.api import model_specs

    seed = 2**31 - 1030
    prog = init_params(model_specs(get_config("chatglm3-6b").reduced()),
                       jax.random.PRNGKey(seed))
    ref = chatglm.make_weights(GLM, seed)
    flat = {"attn.wq": prog["blocks"]["attn"]["wq"],
            "attn.wk": prog["blocks"]["attn"]["wk"],
            "mlp.wo": prog["blocks"]["mlp"]["wo"],
            "norm2": prog["blocks"]["norm2"],
            "embed": prog["embed"], "lm_head": prog["lm_head"]}
    for name, value in flat.items():
        assert value.dtype == ref[name].dtype
        np.testing.assert_array_equal(np.asarray(value, np.float32),
                                      np.asarray(ref[name], np.float32))


def test_reference_follows_served_tokens():
    from repro.launch.serve import run_serve

    seed, prompt_len = 99, 12
    res = run_serve("chatglm3-6b", reduced=True, batch=2,
                    prompt_len=prompt_len, gen=20, seed=seed,
                    profile_policy="off")
    toks = np.asarray(res.tokens)
    ref = np.asarray(chatglm.logits(GLM, chatglm.make_weights(GLM, seed),
                                    toks))
    gap = chatglm.served_gap(ref, toks, prompt_len)
    # bfloat16 serving against float32: small gaps, far below the logits'
    # own spread
    assert 0 <= gap < 0.1 * float(ref.std())

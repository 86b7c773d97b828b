"""The controls: the reference put in the program's place one step below
what the configuration states must read as not correct.

* served model: the reference in float8_e4m3 (below the bfloat16 served);
  at each position of the served tokens, the gap of the token it puts
  first.  At this test's tiny size the readings are smaller than at the
  cell's; the test holds the control to reading at least three times the
  program on every seed, the separation the cell's limit needs.
* simulator: the reference machine without the profiler's stall cycles
  (a broken exactness guarantee) in place of the simulator, through the
  harness's own run.
"""
import json
from pathlib import Path
from unittest import mock

import numpy as np
import pytest

from bench.reference import chatglm, dataflow
from bench.tests._drive import TINY_CAMPAIGN, TINY_GLM, run_cell

BENCH = Path(__file__).resolve().parents[1]
GLM = {**json.loads((BENCH / "configs" / "chatglm3-6b.json").read_text()),
       **TINY_GLM}
RINN = json.loads((BENCH / "configs" / "rinn-t1-zcu102.json").read_text())


@pytest.mark.parametrize("seed", [5, 2**31 + 17, 123456])
def test_fp8_control_reads_far_above_the_program(seed):
    from repro.launch.serve import run_serve

    prompt_len = 16
    res = run_serve("chatglm3-6b", reduced=True, batch=2,
                    prompt_len=prompt_len, gen=32, seed=seed % (2**31 - 1024),
                    profile_policy="off")
    toks = np.asarray(res.tokens)
    w = chatglm.make_weights(GLM, seed % (2**31 - 1024))
    ref = np.asarray(chatglm.logits(GLM, w, toks))
    ctrl = np.asarray(chatglm.logits(GLM, w, toks, quant="fp8"))
    program = chatglm.served_gap(ref, toks, prompt_len)
    control = chatglm.control_gap(ref, ctrl, prompt_len)
    assert control >= 3 * program, (program, control)


def _control_results(sim_lanes):
    """run_sim_batch answered by the reference without interference."""
    from repro.rinn import SimResult

    def run(sim, *, plans, capacity_overrides, profiled, max_cycles):
        graph = _control_results.graph
        m = dataflow.lower(graph, RINN["timing"])
        out = []
        for p, c, f in zip(plans, capacity_overrides, profiled):
            r = dataflow.simulate(m, plan=p, capacities=c, profiled=f,
                                  max_cycles=max_cycles, interference=False)
            out.append(SimResult(consumer_type={}, **r))
        return out
    return run


def test_campaign_control_is_not_correct():
    from bench.traffic import designs

    _control_results.graph = designs.build(RINN["design"],
                                           RINN["design"]["seed"])
    patch = mock.patch("repro.rinn.run_sim_batch", _control_results(None))
    out = run_cell("rinn-t1.campaign", traffic=TINY_CAMPAIGN,
                   workload={"limits": {"checked_lanes": 24}},
                   patches=[patch])
    assert not out["correct"], out["checks"]
    assert out["checks"]["lanes_wrong"]["value"] > 0


def test_cosim_control_is_not_correct():
    from repro.rinn import CosimReport, FifoRow

    def compare(graph, timing, max_cycles=200_000, **kw):
        m = dataflow.lower(graph, RINN["timing"])
        ref = dataflow.simulate(m, max_cycles=max_cycles)
        prof = dataflow.simulate(m, profiled=True, max_cycles=max_cycles,
                                 interference=False)
        rows = [FifoRow(e, "", ref["fifo_max"][e], v)
                for e, v in sorted(prof["fifo_profiled"].items())]
        return CosimReport(rows=rows, cycles_unprofiled=ref["cycles"],
                           cycles_profiled=prof["cycles"], completed=True)

    out = run_cell("rinn-t1.cosim", patches=[
        mock.patch("repro.rinn.compare", compare)])
    assert not out["correct"], out["checks"]
    assert out["checks"]["reports_wrong"]["value"] > 0

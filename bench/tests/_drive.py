"""Run a cell in this process on the CPU at a tiny size, for the tests.

Skips the harness's look for a chip and stands the TPU's peaks in for the
CPU's; everything else is the harness's own path.  Never a measurement.
"""
from __future__ import annotations

import contextlib
import json
from unittest import mock

TINY_GLM = {"num_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
            "multi_query_group_num": 2, "kv_channels": 16,
            "ffn_hidden_size": 128, "padded_vocab_size": 256,
            "program": {"arch": "chatglm3-6b", "reduced": True}}
TINY_DECODE = {"batch": 4, "prompt_len": 16, "gen": 24}
TINY_CAMPAIGN = {"lanes": 48, "fault_free": 16, "pool_calls": 2}


def run_cell(name: str, *, seed: int = 2**31 + 12345, seconds: float = 0.05,
             trace: int = 0, config=None, traffic=None, workload=None,
             patches=()):
    import jax

    from bench import counts, run

    real_setup = run.cell_setup

    def cell_setup(n):
        cell, cfg, tr, wl, e2e, layer = real_setup(n)
        cfg = {**cfg, **(config or {})}
        tr = {**tr, **(traffic or {})}
        wl = json.loads(json.dumps(wl))
        for k, v in (workload or {}).items():
            wl[k] = {**wl[k], **v} if isinstance(v, dict) else v
        return cell, cfg, tr, wl, e2e, layer

    tpu = counts.peaks("TPU v5 lite")
    with contextlib.ExitStack() as stack:
        stack.enter_context(mock.patch.object(run, "cell_setup", cell_setup))
        stack.enter_context(mock.patch.object(
            run, "require_chips", lambda n: jax.devices()[:n]))
        stack.enter_context(mock.patch.object(counts, "peaks",
                                              lambda kind: tpu))
        stack.enter_context(mock.patch.object(run, "configure_jax",
                                              lambda: None))
        for p in patches:
            stack.enter_context(p)
        return run.run(["--workload", name, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", str(trace)])

"""The harness's own run, with the timed path broken underneath, must come
out not correct; and a run without a TPU, or without the program, prints
no result."""
import os
import shutil
import subprocess
import sys
from pathlib import Path
from unittest import mock

import pytest

from bench.tests._drive import TINY_CAMPAIGN, TINY_DECODE, TINY_GLM, run_cell

ROOT = Path(__file__).resolve().parents[2]


def _altered_serve_step(make):
    """Every row's token at one generated step replaced by its neighbour."""
    import jax.numpy as jnp

    def factory(cfg):
        step = make(cfg)

        def serve_step(params, caches, tokens, pos):
            nxt, caches, rows = step(params, caches, tokens, pos)
            bumped = (nxt + 1) % cfg.vocab_size
            return (jnp.where(pos == TINY_DECODE["prompt_len"] + 3, bumped,
                              nxt), caches, rows)
        return serve_step
    return factory


def test_serve_token_altered_where_produced():
    import repro.launch.serve as serve

    ok = run_cell("glm6b.decode.off", config=TINY_GLM, traffic=TINY_DECODE)
    assert ok["correct"], ok["checks"]
    patch = mock.patch.object(serve, "make_serve_step",
                              _altered_serve_step(serve.make_serve_step))
    bad = run_cell("glm6b.decode.off", config=TINY_GLM, traffic=TINY_DECODE,
                   patches=[patch])
    assert not bad["correct"], bad["checks"]
    assert bad["checks"]["logit_gap"]["value"] > ok["checks"]["logit_gap"][
        "value"]


def _altered_unpack(unpack):
    def wrapped(*args, **kwargs):
        res = unpack(*args, **kwargs)
        res.cycles += 1
        return res
    return wrapped


@pytest.mark.parametrize("cell, kw", [
    ("rinn-t1.campaign", dict(traffic=TINY_CAMPAIGN,
                              workload={"limits": {"checked_lanes": 16}})),
    ("rinn-t1.cosim", {}),
])
def test_sim_answer_altered_where_produced(cell, kw):
    import repro.rinn.batchsim as batchsim

    ok = run_cell(cell, **kw)
    assert ok["correct"], ok["checks"]
    patch = mock.patch.object(batchsim, "_unpack",
                              _altered_unpack(batchsim._unpack))
    bad = run_cell(cell, patches=[patch], **kw)
    assert not bad["correct"], bad["checks"]


def _run_script(cwd, tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rinn-t1.cosim",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result(tmp_path):
    out = _run_script(ROOT, tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    bare = tmp_path / "bare"
    bare.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", bare)
    shutil.copytree(ROOT / "bench", bare / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_script(bare, tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""

"""Pipeline parallelism: numeric equivalence + bubble model (subprocess)."""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.distributed.pipeline import pipeline_utilization

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.pipeline import make_pipelined_forward

    S, LPS, D, MB, NM = 4, 2, 16, 2, 8   # 4 stages x 2 layers, 8 microbatches
    mesh = jax.make_mesh((4,), ("stage",), devices=jax.devices(),
                         axis_types=(jax.sharding.AxisType.Auto,))

    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (S, LPS, D, D)) * 0.3

    def block_fn(stage_w, x):           # one stage = LPS tanh layers
        for i in range(LPS):
            x = jnp.tanh(x @ stage_w[i])
        return x

    fwd = make_pipelined_forward(
        block_fn, mesh, "stage",
        param_spec=P("stage", None, None, None),
        x_spec=P(None, None, None))

    xs = jax.random.normal(jax.random.PRNGKey(1), (NM, MB, D))
    out = jax.jit(fwd)(ws, xs)

    # sequential reference: all S*LPS layers in order
    ref = xs
    for s in range(S):
        ref = jax.vmap(lambda x: block_fn(ws[s], x))(ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # the lowering must contain the stage-to-stage collective-permute
    txt = jax.jit(fwd).lower(ws, xs).compile().as_text()
    assert "collective-permute" in txt
    print("OK pipeline matches sequential; collective-permute present")
""")


def test_pipeline_matches_sequential(tmp_path):
    script = tmp_path / "pp_test.py"
    script.write_text(SCRIPT)
    res = subprocess.run(
        [sys.executable, str(script)], cwd=Path(__file__).resolve().parents[1],
        capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-3000:]
    assert "OK pipeline matches sequential" in res.stdout


def test_bubble_model():
    assert pipeline_utilization(1, 4) == pytest.approx(0.25)
    assert pipeline_utilization(8, 4) == pytest.approx(8 / 11)
    assert pipeline_utilization(64, 2) == pytest.approx(64 / 65)
    # more microbatches -> utilization approaches 1
    assert pipeline_utilization(1024, 8) > 0.99

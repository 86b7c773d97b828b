"""Wire-level compressed collective tests (subprocess: needs 8 devices)."""
import subprocess
import sys
import textwrap
from pathlib import Path


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.collectives import compressed_mean, quantize_int8

    mesh = jax.make_mesh((8,), ("pod",), devices=jax.devices(),
                         axis_types=(jax.sharding.AxisType.Auto,))

    def f(x):
        return compressed_mean(x, "pod")

    g = jax.shard_map(f, mesh=mesh, in_specs=P("pod", None),
                      out_specs=P("pod", None), check_vma=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 3.0
    jitted = jax.jit(g)
    out = jitted(x)

    # correctness: close to the exact mean, within int8 quantization error
    exact = jnp.mean(x, axis=0)
    err = float(jnp.max(jnp.abs(out[0] - exact)))
    bound = float(jnp.max(jnp.abs(x)) / 127.0) + 1e-6
    assert err <= bound, (err, bound)

    # wire format: the all-gather payload must be s8 in the lowered HLO
    txt = jitted.lower(x).compile().as_text()
    assert "s8[" in txt and "all-gather" in txt, "no int8 all-gather found"
    lines = [l for l in txt.splitlines() if "all-gather" in l and "s8[" in l]
    assert lines, "all-gather is not int8 on the wire"
    print("OK wire-level int8 all-gather verified; err %.4g <= %.4g"
          % (err, bound))
""")


def test_compressed_mean_wire_level_int8(tmp_path):
    script = tmp_path / "wire_test.py"
    script.write_text(SCRIPT)
    res = subprocess.run(
        [sys.executable, str(script)], cwd=Path(__file__).resolve().parents[1],
        capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK wire-level int8 all-gather verified" in res.stdout


def test_quantize_roundtrip_error_bound():
    import jax
    import jax.numpy as jnp
    from repro.distributed.collectives import dequantize_int8, quantize_int8

    x = jax.random.normal(jax.random.PRNGKey(1), (512,)) * 10
    q, s = quantize_int8(x)
    err = jnp.max(jnp.abs(dequantize_int8(q, s) - x))
    assert float(err) <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6
    assert q.dtype == jnp.int8

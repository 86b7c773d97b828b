"""End-to-end behaviour tests for the paper's system.

Covers: the one-click RINN flow (generate -> profile -> analyze), the
production trainer (train -> crash -> resume bit-exactness of the data
stream), serving, and the dry-run machinery at host scale.
"""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPE_CELLS, cell_applicable, get_config
from repro.core import ProfileCollector
from repro.rinn import RinnConfig, ZCU102, compare, forward, generate_rinn, init_params


def test_paper_flow_end_to_end():
    """RINN generation -> functional profiled run -> streaming cosim."""
    cfg = RinnConfig(n_backbone=5, image_size=6, seed=2, pattern="long_skip",
                     density=0.5)
    g = generate_rinn(cfg)
    params = init_params(g, jax.random.PRNGKey(0))
    y, stream = forward(g, params, jnp.ones((16,)))
    assert y.shape == (5,)

    collector = ProfileCollector()
    decoded = collector.ingest(stream)
    assert len(decoded) == stream.n_signals > 0

    rep = compare(g, ZCU102)
    # headline claims of the paper hold on this system
    assert rep.mean_abs_diff < 3.0
    assert rep.max_abs_diff <= 8
    assert rep.max_depth > 10  # long skips create real FIFO pressure


def test_trainer_resume_preserves_data_stream(tmp_path):
    """Crash/restart mid-training resumes the deterministic batch stream."""
    from repro.launch.train import main as train_main

    ck = tmp_path / "ck"
    l1 = train_main(["--arch", "chatglm3-6b", "--reduced", "--steps", "8",
                     "--batch", "4", "--seq", "32", "--ckpt-dir", str(ck),
                     "--ckpt-every", "4"])
    l2 = train_main(["--arch", "chatglm3-6b", "--reduced", "--steps", "4",
                     "--batch", "4", "--seq", "32", "--ckpt-dir", str(ck),
                     "--ckpt-every", "4"])
    # uninterrupted reference
    ck2 = tmp_path / "ck2"
    ref = train_main(["--arch", "chatglm3-6b", "--reduced", "--steps", "12",
                      "--batch", "4", "--seq", "32", "--ckpt-dir", str(ck2),
                      "--ckpt-every", "100"])
    # the resumed run continues the same loss trajectory as the straight run
    np.testing.assert_allclose(l1 + l2, ref, rtol=2e-4, atol=2e-4)


def test_serve_driver_generates(tmp_path):
    from repro.launch.serve import main as serve_main
    out = serve_main(["--arch", "qwen2.5-14b", "--reduced", "--batch", "2",
                      "--prompt-len", "4", "--gen", "4"])
    assert out.shape == (2, 8)
    assert int(jnp.max(out)) < get_config("qwen2.5-14b").reduced().vocab_size


def test_cell_applicability_rules():
    skipped = []
    for arch in ("chameleon-34b", "mamba2-780m", "zamba2-1.2b"):
        cfg = get_config(arch)
        for cell in SHAPE_CELLS:
            ok, why = cell_applicable(cfg, cell)
            if not ok:
                skipped.append((arch, cell.name))
    # long_500k runs only for the SSM/hybrid archs
    assert ("chameleon-34b", "long_500k") in skipped
    assert ("mamba2-780m", "long_500k") not in skipped
    assert ("zamba2-1.2b", "long_500k") not in skipped


def test_dryrun_artifacts_complete_and_clean():
    """The archived 40-cell x 2-mesh dry-run must be complete: every cell is
    either ok or a documented skip, never an error."""
    art = Path("artifacts/dryrun")
    if not art.exists():
        pytest.skip("dry-run artifacts not present")
    seen = {"single": {}, "multi": {}}
    for p in art.glob("*.json"):
        d = json.loads(p.read_text())
        seen[d["mesh"]][(d["arch"], d["cell"])] = d["status"]
    for mesh, cells in seen.items():
        assert len(cells) == 40, f"{mesh}: {len(cells)} cells"
        assert all(s in ("ok", "skipped") for s in cells.values()), (
            mesh, [k for k, s in cells.items() if s == "error"])
        n_ok = sum(1 for s in cells.values() if s == "ok")
        assert n_ok == 32


def test_input_specs_cover_every_cell():
    from repro.launch.dryrun import input_specs
    for arch in ("qwen2.5-14b", "whisper-base", "mamba2-780m"):
        cfg = get_config(arch)
        for cell in SHAPE_CELLS:
            specs = input_specs(cfg, cell)
            leaves = jax.tree_util.tree_leaves(specs)
            assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
            if cell.kind != "decode":
                tokens_like = leaves[0]
                assert tokens_like.shape[0] == cell.global_batch


def test_compile_cache_defaults_to_fixed_path_in_checkout(monkeypatch):
    from repro.launch.compile_cache import configure_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = configure_compile_cache()
        assert path == str(Path(__file__).resolve().parents[1]
                           / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_is_written_where_env_says(tmp_path):
    import os
    import subprocess
    import sys

    root = Path(__file__).resolve().parents[1]
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import configure_compile_cache\n"
        "print(configure_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")
    cache = tmp_path / "cache"
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(cache),
           "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(root / "src")}
    res = subprocess.run([sys.executable, "-c", script], env=env, cwd=root,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    assert res.stdout.split() == [str(cache)]
    assert any(cache.iterdir())


def test_dryrun_import_leaves_xla_flags_alone():
    import os
    import subprocess
    import sys

    root = Path(__file__).resolve().parents[1]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(root / "src"))
    res = subprocess.run(
        [sys.executable, "-c",
         "import os, repro.launch.dryrun; print(os.environ.get('XLA_FLAGS'))"],
        env=env, cwd=root, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    assert res.stdout.split() == ["None"]
